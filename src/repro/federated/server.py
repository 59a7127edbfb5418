"""The FL coordinator/server loop — EAFL's Fig. 2 architecture.

Runs REAL training: a ResNet speech-keyword classifier (the paper's
workload) on a non-IID label-restricted partition, with the event-driven
energy/timing simulation deciding who participates, who drops out, and how
long each round takes. Local client training is vmapped over the selected
cohort (the TPU-mesh version of the same cohort step lives in repro.launch).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_resnet_speech import CONFIG as RESNET_CONFIG
from repro.configs.paper_resnet_speech import ResNetConfig
from repro.core import (
    ClientPopulation,
    EnergyModel,
    SelectorConfig,
    SelectorState,
    jains_index,
    make_population,
    select,
    stat_utility,
)
from repro.core.clients import pad_population, scatter_stat_util
from repro.core.selection import (
    _auto_pallas,
    _device_select,
    _rank_bits,
    _slot_gather,
)
from repro import spans
from repro.analysis.runtime import setup_transfers
from repro.checkpoint import load_engine_checkpoint, segment_bounds
from repro.data import label_restricted_partition, make_test_set
from repro.federated.aggregation import (
    finite_rows,
    make_server_optimizer,
    server_update,
    tree_finite,
    weighted_delta,
    weighted_sum,
    zero_nonfinite_rows,
)
from repro.federated.faults import (
    N_FAULT_STREAMS,
    FaultConfig,
    apply_faults,
    fault_streams,
    faults_for_round,
)
from repro.federated.controller import (
    ControllerConfig,
    UCBController,
    arm_knobs,
)
from repro.federated.simulation import (
    ENGINES,
    TRAIN_ENGINES,
    BudgetLedger,
    _concat_traj,
    _make_checkpointer,
    _shard_round_step,
    budget_gate,
    cohort_energy_j,
    resolve_aggregation,
    resolve_train_engine,
    round_cost_table,
    run_rounds,
    simulate_round,
    simulate_round_device,
)
from repro.models.resnet import init_resnet, resnet_forward, resnet_loss


@dataclass
class FLConfig:
    selector: SelectorConfig
    n_clients: int = 200
    rounds: int = 100
    local_steps: int = 10
    batch_size: int = 20            # paper: B=20
    client_lr: float = 0.05         # paper: lr=0.05
    server_opt: str = "yogi"        # paper: YoGi
    server_lr: float = 0.05
    samples_per_client: int = 64
    labels_per_client: int = 4      # paper: 10% of 35 labels
    n_classes: int = 35
    input_hw: int = 32
    data_noise: float = 0.5
    eval_every: int = 5
    eval_samples: int = 512
    deadline_s: Optional[float] = None
    seed: int = 0
    model: ResNetConfig = field(default_factory=lambda: RESNET_CONFIG)
    init_battery_low: float = 60.0
    init_battery_high: float = 100.0
    # --- device-workload simulation knobs -------------------------------
    # The paper's edge devices train ResNet-34-class models for ~500 epochs
    # per round; on this CPU container we learn with a small proxy model but
    # simulate the full-size device workload for timing/energy. None ->
    # derive from the actual proxy (fully self-consistent small-scale mode).
    sim_model_bytes: Optional[float] = None    # e.g. 85e6 for ResNet-34
    sim_local_steps: Optional[int] = None      # e.g. 1600 (~500 epochs/B=20)
    idle_busy_fraction: float = 0.02           # unselected-device usage mix
    # --- beyond-paper: recharging availability model --------------------
    # each round a random `plugged_frac` of devices is on a charger and
    # gains `recharge_pct_per_hour` x round-hours; a dropped client whose
    # battery recovers past `rejoin_pct` becomes available again.
    recharge_pct_per_hour: float = 0.0
    plugged_frac: float = 0.25
    rejoin_pct: float = 20.0
    # --- beyond-paper: update compression (repro.compression) -----------
    # shrinks upload time => upload battery cost (Table 1), at the price of
    # a lossy delta. none | int8 | topk; `compression_sparsity` is topk's
    # kept fraction and flows into BOTH the codec and the wire-ratio the
    # energy simulation charges (single source of truth in repro.compression)
    compression: str = "none"
    compression_sparsity: float = 0.05
    # --- beyond-paper: FedProx proximal term on client SGD --------------
    fedprox_mu: float = 0.0
    # --- beyond-paper: over-provisioning (Oort/FedScale style) ----------
    # select ceil(overcommit*K) clients, aggregate only the fastest K
    # successful ones; stragglers beyond K are abandoned (still pay energy)
    overcommit: float = 1.0
    # --- async (FedBuff-style) round engine knobs -----------------------
    # run_fl / run_async_scanned / run_async_sharded: each client
    # completes at its own event-clock time; the server aggregates every
    # `buffer_size` arrivals with 1/(1+staleness)**staleness_power damping
    # and refills freed concurrency slots from the selector. None ->
    # selector.k (the sync-parity limit; with staleness_power=0.0 the
    # async engine then reproduces the synchronous trajectory exactly).
    # Setting buffer_size or max_concurrency is ALSO the async opt-in for
    # the "auto" dispatchers (run_fl, run_rounds, resolve_engine): the
    # knobs have no synchronous meaning, so a config that sets one runs
    # async unless mode="sync" forces otherwise.
    buffer_size: Optional[int] = None
    max_concurrency: Optional[int] = None
    staleness_power: float = 0.5
    # snapshot_ring_size: capacity of the per-version parameter snapshot
    # ring the device-resident async engines carry in-trace (stacked
    # params + version ids + refcounts). None -> max_concurrency, which
    # is provably sufficient (live versions never exceed the concurrency
    # cap); larger values only add headroom/memory. Must be >=
    # max_concurrency. The host async loop keeps snapshots in a python
    # dict and ignores this knob beyond validation.
    snapshot_ring_size: Optional[int] = None
    # --- elastic fault tolerance ----------------------------------------
    # faults: deterministic seed-driven transient client faults
    # (repro.federated.faults) — crash-before-upload with retries,
    # stragglers, corrupted (non-finite) updates. checkpoint_path turns on
    # atomic engine-carry snapshots (a literal `{round}` in the path makes
    # one file per snapshot), checkpoint_every sets the cadence (default:
    # final round only), and resume_from restores a snapshot and continues
    # mid-trajectory — bitwise-identically for the host/scanned/sharded
    # engines (restart parity, tests/test_elastic.py).
    faults: Optional[FaultConfig] = None
    checkpoint_path: Optional[str] = None
    checkpoint_every: Optional[int] = None
    resume_from: Optional[str] = None
    # --- fleet-level energy budget + adaptive knob controller ------------
    # energy_budget_j: fleet-wide joules budget enforced across rounds in
    # EVERY engine (host/scanned/sharded/async). A device-resident
    # cumulative ledger (simulation.BudgetLedger) rides the engine carry —
    # like the RNG chain, so checkpoint/resume restart parity comes free —
    # and a round's cohort is admitted all-or-nothing only when its
    # predicted joules (simulation.cohort_energy_j over the fault-modified
    # cost, so retry surcharges count) still fit. A refused round is inert
    # but the run continues: a later, cheaper cohort may still fit. None =
    # unmetered; accounting always runs and FLHistory.energy_spent_j is
    # always stamped.
    # controller: between-rounds UCB bandit over discrete knob arms
    # (repro.federated.controller) adapting k / buffer_size /
    # staleness_power / compression_sparsity from observed
    # accuracy-per-joule. Host engine only — the fused engines' knobs are
    # compile-time statics.
    energy_budget_j: Optional[float] = None
    controller: Optional[ControllerConfig] = None


def replace_selector_k(sel: SelectorConfig, k: int) -> SelectorConfig:
    return dataclasses.replace(sel, k=k)


def cap_stragglers(outcome, k: int):
    """Over-provisioning cap: keep only the fastest ``k`` *successful*
    clients for aggregation; stragglers beyond ``k`` are abandoned.

    Returns a NEW outcome (never mutates): only ``succeeded`` shrinks.
    Dropout and energy accounting are pre-cap by construction — abandoned
    stragglers already paid their round energy and any battery deaths were
    already counted, so ``new_dropouts`` / ``energy_spent_pct`` /
    ``durations`` pass through untouched.
    """
    order = np.argsort(outcome.durations)
    keep = [i for i in order if outcome.succeeded[i]][:k]
    mask = np.zeros_like(outcome.succeeded)
    mask[keep] = True
    return dataclasses.replace(outcome, succeeded=outcome.succeeded & mask)


def _cohort_train_fn(model_cfg, local_steps: int, batch_size: int, lr: float,
                     fedprox_mu: float = 0.0, compression: str = "none",
                     compression_sparsity: float = 0.05,
                     params_axis: Optional[int] = None):
    """Builds the (un-jitted) client-vmapped local training function.

    ``params_axis=None`` broadcasts one global parameter pytree to the whole
    cohort (the sync server). ``params_axis=0`` gives every client its own
    stacked start parameters — the async server trains each completer from
    the (possibly stale) model version it actually downloaded.

    The host loops jit this via :func:`_local_train_fn`; the fused training
    engines inline the same traced body into their round scan so the
    per-client arithmetic cannot drift between the two paths.
    """
    from repro.compression import compress_delta

    codec_params = ({"sparsity": compression_sparsity}
                    if compression == "topk" else {})

    def one_client(params, x, y, key):
        m = x.shape[0]

        def sgd_step(p, k):
            idx = jax.random.randint(k, (batch_size,), 0, m)
            batch = {"x": x[idx], "y": y[idx]}

            def loss_fn(pp):
                loss, per_sample = resnet_loss(model_cfg, pp, batch)
                if fedprox_mu:
                    # FedProx: mu/2 * ||w - w_global||^2 proximal term
                    prox = sum(jnp.sum(jnp.square(a - b)) for a, b in zip(
                        jax.tree.leaves(pp), jax.tree.leaves(params)))
                    loss = loss + 0.5 * fedprox_mu * prox
                return loss, per_sample

            (loss, per_sample), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p)
            p = jax.tree.map(lambda w, g: w - lr * g, p, grads)
            return p, loss

        keys = jax.random.split(key, local_steps)
        new_params, losses = jax.lax.scan(sgd_step, params, keys)
        delta = jax.tree.map(lambda a, b: a - b, new_params, params)
        if compression != "none":
            delta = compress_delta(compression, delta, **codec_params).delta
        # post-training per-sample losses on the local data -> Oort stat util
        _, per_sample = resnet_loss(model_cfg, new_params, {"x": x, "y": y})
        return delta, per_sample, losses.mean()

    @jax.named_scope("cohort_sgd")
    def cohort(params, xs, ys, keys):
        return jax.vmap(one_client, in_axes=(params_axis, 0, 0, 0))(
            params, xs, ys, keys)

    return cohort


def _local_train_fn(model_cfg, local_steps: int, batch_size: int, lr: float,
                    fedprox_mu: float = 0.0, compression: str = "none",
                    compression_sparsity: float = 0.05,
                    params_axis: Optional[int] = None):
    """Jitted facade over :func:`_cohort_train_fn` for the host loops."""
    return jax.jit(_cohort_train_fn(
        model_cfg, local_steps, batch_size, lr, fedprox_mu, compression,
        compression_sparsity, params_axis))


@jax.named_scope("eval")
def _test_accuracy(model_cfg, params, x, y):
    """Share of the test samples ``x`` the model labels ``y``: every
    engine's eval, inside their steps and out."""
    logits = resnet_forward(model_cfg, params, x)
    return (jnp.argmax(logits, -1) == y).mean()


@dataclass
class FLHistory:
    round: List[int] = field(default_factory=list)
    wall_hours: List[float] = field(default_factory=list)
    round_duration: List[float] = field(default_factory=list)
    test_acc: List[float] = field(default_factory=list)
    train_loss: List[float] = field(default_factory=list)
    cum_dropouts: List[int] = field(default_factory=list)
    fairness: List[float] = field(default_factory=list)
    participation: List[float] = field(default_factory=list)
    mean_battery: List[float] = field(default_factory=list)
    # --- fault/elasticity accounting (repro.federated.faults) -----------
    # retries: upload re-attempts actually made by the round's cohort;
    # quarantined: clients whose delta the server discarded as non-finite;
    # update_skipped: 1 when the round applied NO server update (empty
    # cohort, or the whole aggregate was quarantined)
    retries: List[int] = field(default_factory=list)
    quarantined: List[int] = field(default_factory=list)
    update_skipped: List[int] = field(default_factory=list)
    # --- fleet energy-budget accounting (cfg.energy_budget_j) ------------
    # energy_spent_j: CUMULATIVE joules debited through each round (the
    # engine ledger's f32 chain, so host/scanned values are bitwise equal);
    # budget_exhausted_round: first round the budget gate refused a cohort
    # (None = the budget was never hit);
    # controller_arm: the knob arm pulled each round (cfg.controller runs
    # only — empty otherwise)
    energy_spent_j: List[float] = field(default_factory=list)
    controller_arm: List[int] = field(default_factory=list)
    budget_exhausted_round: Optional[int] = None
    # accuracy of the untrained model, evaluated before round 1 — the pad
    # value for pre-first-eval rounds (never a fake 0.0)
    init_acc: float = float("nan")

    def as_dict(self) -> Dict[str, Any]:
        return {k: (list(v) if isinstance(v, list) else v)
                for k, v in self.__dict__.items()}


def _recharge_step(cfg: FLConfig, pop: ClientPopulation, krecharge,
                   duration_s: float) -> ClientPopulation:
    """Beyond-paper recharging: a random ``plugged_frac`` of devices gains
    charge over the round's wall time; recovered dropouts rejoin. Shared by
    the sync and async server loops.

    ``krecharge`` must be a key dedicated to this round's recharge draw —
    never a key that is also carried into the next round's split (that
    would correlate the plugged-device draw with round r+1's selection and
    training randomness)."""
    if cfg.recharge_pct_per_hour <= 0.0:
        return pop
    kplug = jax.random.fold_in(krecharge, 7)
    plugged = jax.random.bernoulli(kplug, cfg.plugged_frac,
                                   (cfg.n_clients,))
    gain = cfg.recharge_pct_per_hour * duration_s / 3600.0
    battery = jnp.clip(pop.battery_pct + plugged * gain, 0.0, 100.0)
    rejoin = pop.dropped & (battery >= cfg.rejoin_pct)
    return pop.replace(battery_pct=battery, dropped=pop.dropped & ~rejoin)


def _record_test_acc(hist: FLHistory, cfg: FLConfig, rnd: int, params,
                     test_acc_fn) -> None:
    """Eval every ``eval_every`` rounds (and on the last); other rounds pad
    with the last real evaluation — the untrained model's ``init_acc``
    before the first one, never a fake 0.0. Shared by both server loops."""
    if rnd % cfg.eval_every == 0 or rnd == cfg.rounds:
        hist.test_acc.append(float(test_acc_fn(params)))
    else:
        hist.test_acc.append(hist.test_acc[-1] if hist.test_acc
                             else hist.init_acc)


def _engine_setup(cfg: FLConfig, kpop, model_bytes: float):
    """Population + simulated-workload knobs shared by :func:`run_fl` and
    :func:`run_selection_scanned` — one definition so the scanned path's
    trajectory-parity claim can't drift from the host loop."""
    from repro.compression import wire_bytes

    pop = make_population(kpop, cfg.n_clients,
                          init_battery_low=cfg.init_battery_low,
                          init_battery_high=cfg.init_battery_high,
                          samples_per_client=cfg.samples_per_client)
    sim_steps = (cfg.sim_local_steps if cfg.sim_local_steps is not None
                 else cfg.local_steps)
    codec_params = ({"sparsity": cfg.compression_sparsity}
                    if cfg.compression == "topk" else {})
    up_bytes = wire_bytes(model_bytes, cfg.compression, **codec_params)
    energy_model = EnergyModel(busy_fraction=cfg.idle_busy_fraction)
    return pop, sim_steps, up_bytes, energy_model


def _train_meta(cfg: FLConfig, family: str) -> Dict[str, Any]:
    """Checkpoint identity for a TRAINING run. ``family`` groups engines
    whose carries are interchangeable: ``"train-sync"`` for the fused
    scanned/sharded twins (the sharded engine saves the population trimmed
    to ``n_clients``, so its snapshots are portable across device counts
    and across the two engines), ``"train-host"`` for the reference host
    loop (its checkpoint also carries the python-side FLHistory),
    ``"train-async"`` for the device-resident async twins (scanned and
    sharded share one portable carry: the sharded engine trims the
    population/event-state/slot-rank leaves to ``n_clients``), and
    ``"train-async-host"`` for the reference async event loop (plain
    carry plus the python-side FLHistory). The async families extend the
    meta with the normalized FedBuff knobs
    (:func:`repro.federated.async_server._async_train_meta`)."""
    return {
        "family": family,
        "n_clients": int(cfg.n_clients),
        "rounds": int(cfg.rounds),
        "kind": cfg.selector.kind,
        "k": int(cfg.selector.k),
        "seed": int(cfg.seed),
        "deadline_s": (None if cfg.deadline_s is None
                       else float(cfg.deadline_s)),
        "overcommit": float(cfg.overcommit),
        "compression": cfg.compression,
        "server_opt": cfg.server_opt,
        "faults": (None if cfg.faults is None
                   else dataclasses.asdict(cfg.faults)),
        "energy_budget_j": (None if cfg.energy_budget_j is None
                            else float(cfg.energy_budget_j)),
    }


@spans.span("run_fl")
def run_fl(cfg: FLConfig, verbose: bool = False,
           mode: str = "auto", engine: str = "auto") -> FLHistory:
    """Run the full FL experiment (REAL training).

    ``mode`` resolves through the same dispatcher as the engine-level
    :func:`repro.federated.run_rounds` (``resolve_aggregation``):
    ``"sync"`` is the paper's synchronous round loop, ``"async"`` the
    FedBuff-style buffered-asynchronous server
    (:mod:`repro.federated.async_server`, knobs ``cfg.buffer_size`` /
    ``cfg.max_concurrency`` / ``cfg.staleness_power``), and the default
    ``"auto"`` picks async exactly when ``cfg.buffer_size`` or
    ``cfg.max_concurrency`` is set (``staleness_power`` alone does not
    opt in — it has a meaningful default and is only consulted once the
    async loop runs). Both loops share the population, energy model, and
    fused round core, so their histories are directly comparable (and in
    the ``buffer_size == max_concurrency == k, staleness_power=0`` limit
    the async loop's selection/battery/dropout trajectory reproduces the
    sync loop's).

    ``engine`` picks the synchronous *training* engine through
    :func:`repro.federated.resolve_train_engine`: ``"host"`` is this
    module's reference Python round loop, ``"scanned"`` the fully fused
    device-resident scan (:func:`run_fl_scanned`) and ``"sharded"`` its
    `clients`-mesh twin (:func:`run_fl_sharded`); all three produce the
    same trajectory within float tolerance (``tests/
    test_training_engines.py``). In async mode the same names pick the
    FedBuff engine: ``"host"`` the reference event loop
    (:func:`repro.federated.async_server.run_fl_async`), ``"scanned"``
    the device-resident event scan with the in-carry snapshot ring
    (:func:`run_fl_async_scanned`) and ``"sharded"`` its `clients`-mesh
    twin (:func:`run_fl_async_sharded`); flush/refill/version
    trajectories are index-for-index identical across the three
    (``tests/test_async_training_engines.py``).
    """
    if mode in ENGINES:
        # run_fl is the training front door — selection-only engine names
        # go through repro.federated.run_rounds, not here
        raise ValueError(
            f"run_fl takes 'auto'/'sync'/'async', not the engine name "
            f"{mode!r}; force engines via repro.federated.run_rounds")
    mode = resolve_aggregation(mode, cfg.buffer_size, cfg.max_concurrency)
    engine = resolve_train_engine(
        cfg.n_clients, jax.device_count(), mode=mode, engine=engine)
    if cfg.controller is not None and (mode == "async" or engine != "host"):
        # the controller turns knobs that are compile-time statics in the
        # fused engines and structural in the async event loop — it drives
        # the synchronous host loop only
        raise ValueError(
            f"cfg.controller runs only in the synchronous host loop "
            f"(resolved mode={mode!r}, engine={engine!r}); use "
            f"run_fl(cfg, mode='sync', engine='host')")
    if mode == "async":
        from repro.federated.async_server import (
            run_fl_async, run_fl_async_scanned, run_fl_async_sharded)
        if engine == "scanned":
            return run_fl_async_scanned(cfg, verbose=verbose)
        if engine == "sharded":
            return run_fl_async_sharded(cfg, verbose=verbose)
        return run_fl_async(cfg, verbose=verbose)
    if engine == "scanned":
        return run_fl_scanned(cfg, verbose=verbose)
    if engine == "sharded":
        return run_fl_sharded(cfg, verbose=verbose)
    key = jax.random.PRNGKey(cfg.seed)
    kpop, kdata, kmodel, ktest, kloop = jax.random.split(key, 5)

    data = label_restricted_partition(
        kdata, cfg.n_clients, cfg.samples_per_client, cfg.n_classes,
        cfg.labels_per_client, cfg.input_hw, noise=cfg.data_noise)
    test = make_test_set(ktest, cfg.eval_samples, cfg.n_classes, cfg.input_hw,
                         noise=cfg.data_noise)

    params = init_resnet(kmodel, cfg.model)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    model_bytes = (cfg.sim_model_bytes if cfg.sim_model_bytes is not None
                   else n_params * 4.0)
    opt = make_server_optimizer(cfg.server_opt, cfg.server_lr)
    opt_state = opt.init(params)

    pop, sim_steps, up_bytes, energy_model = _engine_setup(cfg, kpop,
                                                           model_bytes)
    sel_state = SelectorState.create(cfg.selector)
    local_train = _local_train_fn(cfg.model, cfg.local_steps,
                                  cfg.batch_size, cfg.client_lr,
                                  cfg.fedprox_mu, cfg.compression,
                                  cfg.compression_sparsity)

    @jax.jit
    def test_acc_fn(p):
        return _test_accuracy(cfg.model, p, test["x"], test["y"])

    # the round-invariant (time, cost) table: both columns depend only on
    # immutable population fields, so the per-round predicted_round_cost_pct
    # recompute was pure dispatch overhead — hoist it through the engines'
    # round_cost_table and reuse the cost column as the selector's
    # predicted cost every round
    t_total, pred_cost = round_cost_table(pop, energy_model, model_bytes,
                                          sim_steps, cfg.batch_size, up_bytes)
    del t_total  # the host simulate_round recomputes its own copy

    ctrl = None if cfg.controller is None else UCBController(cfg.controller)
    # per-sparsity (wire bytes, predicted cost, train fn) tables for arms
    # that move compression_sparsity — the cost column depends only on
    # immutable population fields, so each distinct sparsity is built once
    _arm_tables: Dict[float, tuple] = {}

    def arm_tables(sparsity: float):
        if sparsity not in _arm_tables:
            from repro.compression import wire_bytes
            ub = wire_bytes(model_bytes, cfg.compression,
                            **({"sparsity": sparsity}
                               if cfg.compression == "topk" else {}))
            _, pc = round_cost_table(pop, energy_model, model_bytes,
                                     sim_steps, cfg.batch_size, ub)
            tf = _local_train_fn(cfg.model, cfg.local_steps, cfg.batch_size,
                                 cfg.client_lr, cfg.fedprox_mu,
                                 cfg.compression, sparsity)
            _arm_tables[sparsity] = (ub, pc, tf)
        return _arm_tables[sparsity]

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def server_step(p, agg, o_state):
        # donating params/opt_state means the loop never holds two copies
        # of model + optimizer state across the update
        return server_update(p, agg, opt, o_state)

    meta = _train_meta(cfg, "train-host")
    ck = _make_checkpointer(cfg.checkpoint_path, cfg.checkpoint_every,
                            cfg.rounds, meta)
    start = 0
    if cfg.resume_from:
        templates = {"params": params, "opt_state": opt_state, "pop": pop,
                     "st": sel_state.canonical(), "kloop": kloop}
        start, state, saved, _ = load_engine_checkpoint(
            cfg.resume_from, templates, expect_meta=meta)
        params, opt_state, pop = (state["params"], state["opt_state"],
                                  state["pop"])
        sel_state, kloop = state["st"], state["kloop"]
        hist = FLHistory(**saved["hist"])
        wall = float(saved["wall"])
        cum_drop = int(saved["cum_drop"])
        last_loss = float(saved["last_loss"])
        # the ledger's f32 chain round-trips exactly through the float
        # history entry, so the resumed gate decisions match bitwise
        spent = hist.energy_spent_j[-1] if hist.energy_spent_j else 0.0
        probe_acc = float(saved.get("probe_acc", hist.init_acc))
        if ctrl is not None and "ctrl" in saved:
            ctrl.load_state(saved["ctrl"])
    else:
        hist = FLHistory()
        # evaluate the untrained model once so pre-first-eval rounds report
        # a real accuracy instead of a fake 0.0 (time-to-accuracy curves)
        hist.init_acc = float(test_acc_fn(params))
        wall = 0.0
        cum_drop = 0
        last_loss = float("nan")
        spent = 0.0
        probe_acc = hist.init_acc

    for rnd in range(start + 1, cfg.rounds + 1):
        # krecharge is a dedicated per-round key: the recharge draw must
        # not share randomness with the carry that seeds round r+1
        # (prefix-stable threefry keeps kloop/ksel/ktrain identical to the
        # historical 3-way split, so only recharge draws moved)
        kloop, ksel, ktrain, krecharge = jax.random.split(kloop, 4)
        arm = arm_i = None
        arm_k = cfg.selector.k
        rnd_up_bytes, rnd_pred_cost, rnd_train = (up_bytes, pred_cost,
                                                  local_train)
        if ctrl is not None:
            # the bandit pulls an arm BEFORE the round, so every knob it
            # moves (k / sparsity here, buffer/staleness below) shapes this
            # round's selection, energy, and aggregation; an all-inherit
            # arm leaves every value identical to the controller-free run
            arm_i = ctrl.choose(rnd)
            arm = cfg.controller.arms[arm_i]
            arm_k = int(arm_knobs(cfg.selector.k, arm.k))
            if arm.compression_sparsity is not None:
                rnd_up_bytes, rnd_pred_cost, rnd_train = arm_tables(
                    float(arm.compression_sparsity))
        n_pick = int(np.ceil(arm_k * cfg.overcommit))
        sel_cfg = cfg.selector if n_pick == cfg.selector.k else \
            replace_selector_k(cfg.selector, n_pick)
        selected, sel_state = select(ksel, sel_cfg, sel_state, pop,
                                     rnd_pred_cost)
        if len(selected) == 0:
            break
        spent_before = spent
        pop, outcome = simulate_round(
            pop, selected, energy_model, model_bytes,
            sim_steps, cfg.batch_size, rnd, cfg.deadline_s, rnd_up_bytes,
            faults=cfg.faults, energy_budget_j=cfg.energy_budget_j,
            spent_j=spent)
        spent = outcome.spent_after_j
        if not outcome.admitted and hist.budget_exhausted_round is None:
            hist.budget_exhausted_round = rnd
        cum_drop += outcome.new_dropouts
        agg_cap = (arm_k if arm is None or arm.buffer_size is None
                   else min(arm_k, int(arm.buffer_size)))
        if cfg.overcommit > 1.0 or agg_cap < n_pick:
            # keep only the fastest agg_cap successful clients (stragglers
            # beyond the cap are abandoned — they still paid the energy);
            # the outcome is replaced, not mutated: the pre-cap `succeeded`
            # already fed the dropout accounting above. agg_cap shrinks
            # below k only when a controller arm sets buffer_size.
            outcome = cap_stragglers(outcome, agg_cap)

        pop = _recharge_step(cfg, pop, krecharge, outcome.round_duration)

        succ = outcome.selected[outcome.succeeded]
        skipped = 1
        n_quar = 0
        if len(succ) > 0:
            xs = data["x"][succ]
            ys = data["y"][succ]
            keys = jax.random.split(ktrain, len(succ))
            deltas, per_sample, mean_losses = rnd_train(params, xs, ys, keys)
            if cfg.faults is not None and cfg.faults.active:
                # corrupted-upload fault: the client trained and paid the
                # energy, but the delta that arrives is garbage
                bad = jnp.asarray(outcome.corrupt[outcome.succeeded])
                deltas = jax.tree.map(
                    lambda d: jnp.where(
                        bad.reshape((-1,) + (1,) * (d.ndim - 1)),
                        jnp.nan, d), deltas)
            # non-finite quarantine: zero both the weight AND the delta row
            # (0 * nan == nan), so weighted_delta renormalizes over the
            # survivors; a last-resort gate keeps even a finite-per-client
            # overflow out of the global params
            finite = finite_rows(deltas)
            weights = np.asarray(pop.n_samples)[succ].astype(np.float32)
            w = jnp.where(finite, jnp.asarray(weights), 0.0)
            if (arm is not None and arm.staleness_power is not None
                    and arm.staleness_power > 0.0):
                # FedBuff-style damping on the sync cohort: later arrivals
                # (arrival rank by round duration) count less —
                # weighted_delta renormalizes, so only relative damping
                # matters
                dur = np.asarray(outcome.durations)[outcome.succeeded]
                rank = np.argsort(np.argsort(dur, kind="stable"),
                                  kind="stable")
                w = w * jnp.asarray(
                    (1.0 + rank.astype(np.float32))
                    ** np.float32(-arm.staleness_power))
            agg = weighted_delta(zero_nonfinite_rows(deltas, finite), w)
            n_quar = int(jnp.sum(~finite))
            if bool(finite.any()) and bool(tree_finite(agg)):
                params, opt_state = server_step(params, agg, opt_state)
                skipped = 0
            # update Oort statistical utility for participants (functional
            # scatter — the population pytree stays device-resident);
            # quarantined clients contribute no utility update
            su = stat_utility(per_sample, w)
            pop = scatter_stat_util(pop, jnp.asarray(succ), finite, su)
            last_loss = float(mean_losses.mean())

        wall += outcome.round_duration / 3600.0
        hist.round.append(rnd)
        hist.wall_hours.append(wall)
        hist.round_duration.append(outcome.round_duration)
        hist.cum_dropouts.append(cum_drop)
        hist.fairness.append(float(jains_index(pop.times_selected)))
        hist.participation.append(float(outcome.succeeded.mean()))
        hist.mean_battery.append(float(pop.battery_pct.mean()))
        hist.train_loss.append(last_loss)
        hist.retries.append(int(outcome.retries))
        hist.quarantined.append(n_quar)
        hist.update_skipped.append(skipped)
        hist.energy_spent_j.append(spent)
        if ctrl is not None:
            hist.controller_arm.append(arm_i)
            # reward probe: a pure extra eval (consumes no RNG), so the
            # controller's bookkeeping cannot perturb the trajectory
            acc_now = float(test_acc_fn(params))
            ctrl.update(arm_i, acc_now - probe_acc, spent - spent_before)
            probe_acc = acc_now
        _record_test_acc(hist, cfg, rnd, params, test_acc_fn)
        if verbose and rnd % 10 == 0:
            print(f"[{cfg.selector.kind}] r={rnd} acc={hist.test_acc[-1]:.3f} "
                  f"loss={last_loss:.3f} drop={cum_drop} "
                  f"fair={hist.fairness[-1]:.3f} wall={wall:.2f}h")
        if ck and ck.due(rnd):
            # kloop here is the carry that seeds round rnd+1, so a resumed
            # run re-enters the identical RNG chain
            ck_data = {"hist": hist.as_dict(), "wall": wall,
                       "cum_drop": cum_drop, "last_loss": last_loss}
            if ctrl is not None:
                ck_data["ctrl"] = ctrl.state_dict()
                ck_data["probe_acc"] = probe_acc
            ck.save(rnd,
                    {"params": params, "opt_state": opt_state, "pop": pop,
                     "st": sel_state, "kloop": kloop},
                    ck_data)
    return hist


# ------------------------------------------------------- fused training scan
# The device-resident training engine: one jitted lax.scan advances the FULL
# round — selection → energy/dropout simulation → masked fixed-width cohort
# local SGD → compressed aggregation → server update → eval — with params,
# server optimizer state, the population (incl. Oort stat_util) and the RNG
# chain all in the scan carry. Zero per-round host transfers: the host sees
# one device call per experiment instead of ~10 dispatches per round.
#
# Parity contract with the host loop (tests/test_training_engines.py):
#   * the RNG chain is the host chain: `kloop, ksel, ktrain, krecharge =
#     split(kloop, 4)` per round, and the slot with success-rank j trains
#     with `split(ktrain, n_slots)[j]` — partitionable threefry is
#     prefix-stable, so this equals the host's dynamic
#     `split(ktrain, n_succ)[j]` draw bitwise;
#   * failed/abandoned slots train dead weight: their deltas enter
#     `weighted_delta` with weight exactly 0.0, which contributes exactly
#     0.0 to the normalized tensordot — masked fixed-width aggregation is
#     arithmetic-identical to the host's compacted dynamic cohort;
#   * the over-provisioning cap is `lax.top_k` over (-duration | mask),
#     the device twin of `cap_stragglers`' argsort-and-filter;
#   * the server update is computed unconditionally but gated with a
#     `where(ok, ...)` where `ok = good.any() & tree_finite(agg)` — some
#     non-quarantined client succeeded and the aggregate is finite — since
#     the adaptive optimizers are NOT no-ops on zero deltas (yogi's
#     sign-based v update, bias-correction t), and the host loop skips the
#     update entirely on empty or fully-quarantined cohorts;
#   * width-sensitive stat reductions happen OUTSIDE the scan, from the
#     per-slot masks/losses in the trajectory (`_history_from_traj`):
#     participation in f64 and train_loss as the same compacted-width f32
#     mean the host takes — an in-scan reduction over the fixed slot axis
#     would round differently whenever n_slots != n_succ.
# One host-visible difference remains: the host loop `break`s when
# selection returns no candidates; the scan always runs `rounds` rounds
# (the extra rounds are inert — empty cohort, gated update).


@functools.lru_cache(maxsize=8)
def _fused_runner(model_cfg: ResNetConfig, sel_cfg: SelectorConfig,
                  agg_k: int, energy_model: EnergyModel,
                  deadline_s: Optional[float],
                  local_steps: int, batch_size: int, client_lr: float,
                  fedprox_mu: float, compression: str, sparsity: float,
                  server_opt: str, server_lr: float,
                  recharge_pct_per_hour: float, plugged_frac: float,
                  rejoin_pct: float, faults: Optional[FaultConfig],
                  energy_budget_j: Optional[float],
                  use_pallas: bool, interpret: bool):
    """Cached jitted fused training scan (hashable statics only, mirroring
    ``simulation._scanned_runner``). ``sel_cfg.k`` is the over-provisioned
    slot count ``ceil(k * overcommit)``; ``agg_k`` the aggregation cap
    (the pre-overcommit k).

    Returns ``(run, evaluate)``. ``run(do_eval, carry, ...)`` advances the
    full training carry ``(params, opt_state, pop, st, kloop, last_acc,
    ledger)`` by ``len(do_eval)`` rounds — segment-callable: because the RNG chain
    lives in the carry, two chained segments are bitwise-identical to one
    long scan, which is what makes checkpoint/resume restart-parity exact.
    ``do_eval`` carries the absolute-round eval schedule (computed by the
    wrapper, so segments agree with the uninterrupted run). ``evaluate``
    is the matching standalone test-accuracy jit (init eval / resume)."""
    opt = make_server_optimizer(server_opt, server_lr)
    cohort = _cohort_train_fn(model_cfg, local_steps, batch_size, client_lr,
                              fedprox_mu, compression, sparsity)
    faulty = faults is not None and faults.active

    @jax.jit
    def evaluate(params, test_x, test_y):
        return _test_accuracy(model_cfg, params, test_x, test_y)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(do_eval, carry, data_x, data_y, test_x, test_y, t_total, cost):
        n = carry[2].n

        def eval_acc(p):
            return _test_accuracy(model_cfg, p, test_x, test_y)

        def scan_step(carry, do_eval):
            params, opt_state, pop, st, kloop, last_acc, ledger = carry
            kloop, ksel, ktrain, krecharge = jax.random.split(kloop, 4)
            idx, chosen, st = _device_select(ksel, sel_cfg, st, pop, cost,
                                             use_pallas, interpret)
            # selection scored on the CLEAN cost above (the forecast can't
            # see transient faults); the simulation runs on the
            # fault-modified durations/costs, like the host simulate_round
            t_eff, cost_eff, draw = faults_for_round(faults, st.round,
                                                     t_total, cost)
            sel_mask = jnp.zeros((n,), bool).at[
                jnp.where(chosen, idx, n)].set(True, mode="drop")
            # budget gate on the fault-modified cost (retry surcharges
            # count), BEFORE simulation: a refused round zeroes the cohort
            # mask, so the whole round body below runs inert
            round_j = cohort_energy_j(pop, sel_mask, cost_eff)
            sel_mask, _admit, ledger = budget_gate(
                sel_mask, round_j, ledger, energy_budget_j, st.round)
            pop, dev = simulate_round_device(
                pop, sel_mask, t_eff, cost_eff, st.round, energy_model,
                deadline_s, fail_mask=None if draw is None else draw.fail)
            ledger = ledger._replace(
                spent_j=ledger.spent_j + dev.energy_spent_j)
            n_slots = idx.shape[0]
            slot_succ = dev.succeeded[idx] & chosen
            if n_slots > agg_k:
                # keep the fastest agg_k successful slots (top_k breaks
                # duration ties lowest-slot-first, like the host argsort);
                # ranked on the fault-modified durations, like the host's
                # cap_stragglers over outcome.durations
                g = jnp.where(slot_succ, -t_eff[idx], -jnp.inf)
                _, keep_slots = jax.lax.top_k(g, agg_k)
                keep = jnp.zeros((n_slots,), bool).at[keep_slots].set(True)
                mask = slot_succ & keep
            else:
                mask = slot_succ
            if recharge_pct_per_hour > 0.0:
                kplug = jax.random.fold_in(krecharge, 7)
                plugged = jax.random.bernoulli(kplug, plugged_frac, (n,))
                gain = recharge_pct_per_hour * dev.round_duration / 3600.0
                battery = jnp.clip(pop.battery_pct + plugged * gain,
                                   0.0, 100.0)
                rejoin = pop.dropped & (battery >= rejoin_pct)
                pop = pop.replace(battery_pct=battery,
                                  dropped=pop.dropped & ~rejoin)
            # masked fixed-width cohort: every slot trains, dead slots are
            # zero-weighted out of the aggregation; success-rank key
            # assignment reproduces the host's dynamic split bitwise
            ranks = jnp.clip(jnp.cumsum(mask) - 1, 0, n_slots - 1)
            keys = jax.random.split(ktrain, n_slots)[ranks]
            deltas, per_sample, mean_losses = cohort(
                params, data_x[idx], data_y[idx], keys)
            if faulty:
                # corrupted-upload fault: the slot trained (and paid), but
                # the delta that reaches the server is non-finite
                bad = draw.corrupt[idx] & mask
                deltas = jax.tree.map(
                    lambda d: jnp.where(
                        bad.reshape((n_slots,) + (1,) * (d.ndim - 1)),
                        jnp.nan, d), deltas)
            # non-finite quarantine (always on): zero the weight AND the
            # row (0 * nan == nan), renormalize over survivors, and gate
            # the whole update on the aggregate staying finite — identical
            # to the host loop's quarantine block
            with jax.named_scope("aggregate"):
                finite = finite_rows(deltas)
                good = mask & finite
                w = jnp.where(good, pop.n_samples[idx].astype(jnp.float32),
                              0.0)
                agg = weighted_delta(zero_nonfinite_rows(deltas, finite), w)
                new_params, new_opt = server_update(params, agg, opt,
                                                    opt_state)
                ok = good.any() & tree_finite(agg)
                params = jax.tree.map(
                    lambda a, b: jnp.where(ok, a, b), new_params, params)
                opt_state = jax.tree.map(
                    lambda a, b: jnp.where(ok, a, b), new_opt, opt_state)
            su = stat_utility(per_sample, w)
            pop = scatter_stat_util(pop, idx, good, su)
            last_acc = jax.lax.cond(do_eval, eval_acc,
                                    lambda _: last_acc, params)
            retries = (jnp.sum(jnp.where(sel_mask, draw.retries, 0))
                       .astype(jnp.int32) if faulty else jnp.int32(0))
            out = {
                "selected": idx,
                "chosen": chosen,
                "succeeded": mask,
                "round_duration": dev.round_duration,
                "new_dropouts": dev.new_dropouts,
                "energy_spent_pct": dev.energy_spent_pct,
                "mean_battery": jnp.mean(pop.battery_pct),
                "fairness": jains_index(pop.times_selected),
                # per-slot losses (masked); the host-facing train_loss is
                # reduced OUTSIDE the scan over the compacted slots so the
                # reduction width (and hence f32 rounding) matches the host
                # loop exactly even when n_slots > agg_k (overcommit)
                "slot_losses": jnp.where(mask, mean_losses, 0.0),
                "test_acc": last_acc,
                "retries": retries,
                "quarantined": jnp.sum(mask & ~finite).astype(jnp.int32),
                "update_skipped": (~ok).astype(jnp.int32),
                # cumulative f32 ledger value — emitting the chain itself
                # (not per-round deltas summed host-side) keeps the
                # history bitwise equal to the host loop's spent_after_j
                "energy_spent_j": ledger.spent_j,
                "budget_exhausted": ledger.exhausted_round,
            }
            return (params, opt_state, pop, st, kloop, last_acc,
                    ledger), out

        return jax.lax.scan(scan_step, carry, do_eval)

    return run, evaluate


def _fused_setup(cfg: FLConfig):
    """Shared data/model/population setup for the fused training engines —
    the exact :func:`run_fl` preamble (same key split, same builders), so
    engine trajectories start from identical state. Callers open the
    ``fl.setup`` span this records its first three children in."""
    key = jax.random.PRNGKey(cfg.seed)
    kpop, kdata, kmodel, ktest, kloop = jax.random.split(key, 5)
    with spans.span("fl.setup.data"):
        data = label_restricted_partition(
            kdata, cfg.n_clients, cfg.samples_per_client, cfg.n_classes,
            cfg.labels_per_client, cfg.input_hw, noise=cfg.data_noise)
        test = make_test_set(ktest, cfg.eval_samples, cfg.n_classes,
                             cfg.input_hw, noise=cfg.data_noise)
    with spans.span("fl.setup.model"):
        params = init_resnet(kmodel, cfg.model)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        model_bytes = (cfg.sim_model_bytes
                       if cfg.sim_model_bytes is not None
                       else n_params * 4.0)
        opt = make_server_optimizer(cfg.server_opt, cfg.server_lr)
        opt_state = opt.init(params)
    with spans.span("fl.setup.fleet"):
        pop, sim_steps, up_bytes, energy_model = _engine_setup(
            cfg, kpop, model_bytes)
    return (kloop, data, test, params, opt_state, pop, sim_steps, up_bytes,
            energy_model, model_bytes)


def _fused_statics(cfg: FLConfig) -> tuple:
    """The hashable static tail shared by :func:`_fused_runner` and
    :func:`_sharded_fused_runner`."""
    n_pick = int(np.ceil(cfg.selector.k * cfg.overcommit))
    sel_cfg = cfg.selector if n_pick == cfg.selector.k else \
        replace_selector_k(cfg.selector, n_pick)
    return (sel_cfg, int(cfg.selector.k),
            EnergyModel(busy_fraction=cfg.idle_busy_fraction),
            None if cfg.deadline_s is None else float(cfg.deadline_s),
            int(cfg.local_steps),
            int(cfg.batch_size), float(cfg.client_lr), float(cfg.fedprox_mu),
            cfg.compression, float(cfg.compression_sparsity),
            cfg.server_opt, float(cfg.server_lr),
            float(cfg.recharge_pct_per_hour), float(cfg.plugged_frac),
            float(cfg.rejoin_pct), cfg.faults,
            None if cfg.energy_budget_j is None
            else float(cfg.energy_budget_j))


def _reject_async_knobs(cfg: FLConfig, name: str) -> None:
    if cfg.buffer_size is not None or cfg.max_concurrency is not None:
        raise ValueError(
            f"{name} is a synchronous engine; cfg.buffer_size / "
            f"cfg.max_concurrency opt into the async server — use "
            f"run_fl(cfg) and let the dispatcher route it")
    if cfg.controller is not None:
        raise ValueError(
            f"{name} compiles its knobs as statics; the adaptive "
            f"controller (cfg.controller) runs only in the host loop — "
            f"use run_fl(cfg, engine='host')")


def _history_from_traj(cfg: FLConfig, init_acc: float, traj) -> FLHistory:
    """Assemble :class:`FLHistory` from a fused-engine trajectory. The only
    host float work is the f64 wall-clock accumulation, done exactly like
    the host loop (per-round /3600 then cumulative sum).

    Counts, in the open span, the trajectory's cohort local SGD:
    ``sgd.slots_trained``, every slot of every round (the fused engines
    train dead slots too), and ``sgd.slots_aggregated``, the slots whose
    delta reached an applied server update."""
    hist = FLHistory(init_acc=init_acc)
    dur = np.asarray(traj["round_duration"])
    hist.round = list(range(1, cfg.rounds + 1))
    hist.wall_hours = [float(x) for x in
                       np.cumsum(dur.astype(np.float64) / 3600.0)]
    hist.round_duration = [float(x) for x in dur]
    hist.cum_dropouts = [int(x) for x in
                         np.cumsum(np.asarray(traj["new_dropouts"]))]
    # participation in f64 from the per-slot masks — bitwise-equal to the
    # host loop's `float(outcome.succeeded.mean())` over the cohort
    n_succ = np.asarray(traj["succeeded"]).sum(axis=1).astype(np.float64)
    n_sel = np.asarray(traj["chosen"]).sum(axis=1).astype(np.float64)
    hist.participation = [float(x) for x in
                          n_succ / np.maximum(n_sel, 1.0)]
    aggregated = ((n_succ - np.asarray(traj["quarantined"]))
                  * (1 - np.asarray(traj["update_skipped"])))
    spans.count("sgd.slots_trained", np.asarray(traj["succeeded"]).size)
    spans.count("sgd.slots_aggregated", aggregated.sum())
    # train_loss: reduce the compacted per-slot losses with the SAME jnp
    # f32 mean the host loop uses (`mean_losses.mean()` over the dynamic
    # cohort) — reducing in-scan over the fixed slot axis would associate
    # the f32 sum differently whenever n_slots != n_succ. Empty rounds
    # retain the previous loss, like the host loop's `last_loss`.
    slot_losses = np.asarray(traj["slot_losses"])
    succ_mask = np.asarray(traj["succeeded"])
    last_loss = float("nan")
    hist.train_loss = []
    for r in range(slot_losses.shape[0]):
        m = succ_mask[r]
        if m.any():
            # explicit device round-trip (not jnp.asarray/float) so the
            # f32 jnp mean — required for bitwise host-loop parity — is
            # still legal under strict_mode's transfer guard
            last_loss = float(jax.device_get(
                jnp.mean(jax.device_put(slot_losses[r][m]))))
        hist.train_loss.append(last_loss)
    for name in ("test_acc", "fairness", "mean_battery"):
        setattr(hist, name, [float(x) for x in np.asarray(traj[name])])
    for name in ("retries", "quarantined", "update_skipped"):
        if name in traj:
            setattr(hist, name, [int(x) for x in np.asarray(traj[name])])
    if "energy_spent_j" in traj:
        # the per-round values ARE the cumulative f32 ledger chain (the
        # f32->f64 float() round-trip is exact, so host parity is bitwise)
        hist.energy_spent_j = [float(x) for x in
                               np.asarray(traj["energy_spent_j"])]
    if "budget_exhausted" in traj:
        last = int(np.asarray(traj["budget_exhausted"])[-1])
        hist.budget_exhausted_round = last if last > 0 else None
    return hist


def _print_fused_history(cfg: FLConfig, hist: FLHistory) -> None:
    """Post-hoc twin of the host loop's every-10-rounds progress line (the
    fused engines have nothing to print per round — that's the point).
    Iterates the recorded rounds, not ``cfg.rounds``: async histories are
    truncated at quiescence."""
    for rnd in range(10, len(hist.round) + 1, 10):
        i = rnd - 1
        print(f"[{cfg.selector.kind}] r={rnd} acc={hist.test_acc[i]:.3f} "
              f"loss={hist.train_loss[i]:.3f} drop={hist.cum_dropouts[i]} "
              f"fair={hist.fairness[i]:.3f} wall={hist.wall_hours[i]:.2f}h")


_TRAIN_CARRY = ("params", "opt_state", "pop", "st", "kloop", "last_acc",
                "ledger")


def _fused_do_eval(cfg: FLConfig, a: int, b: int) -> jnp.ndarray:
    """Eval schedule for absolute rounds ``(a, b]`` — computed from the
    absolute round numbers so a resumed segment evaluates on exactly the
    rounds the uninterrupted run would. The host->device transfer is
    explicit (device_put) so the segment loop stays legal under
    ``analysis.runtime.strict_mode``."""
    rr = np.arange(a + 1, b + 1)
    return jax.device_put(((rr % cfg.eval_every) == 0) | (rr == cfg.rounds))


def _untrained_acc(evaluate, params, test) -> Tuple[jnp.ndarray, float]:
    """The untrained model's test accuracy, on the device (the carry's
    first ``last_acc``) and on the host (the history's ``init_acc``)."""
    with spans.span("fl.setup.eval0"):
        acc0 = evaluate(params, test["x"], test["y"])
        return acc0, float(jax.device_get(acc0))


def _run_fused_elastic(cfg: FLConfig, run, carry0, init_acc: float,
                       run_args, resume_templates, save_state, meta=None,
                       history_fn=None, carry_names=_TRAIN_CARRY,
                       capture=None) -> FLHistory:
    """Shared segment/checkpoint/resume driver for the fused training
    engines (sync scanned/sharded and their async twins). ``carry0`` is
    the fresh carry tuple laid out as ``carry_names``, ``init_acc`` its
    untrained accuracy (a resumed run takes the saved one); ``run_args`` the
    engine's per-call data tail; ``resume_templates["restore"](state)``
    maps loaded checkpoint state back onto an engine carry (with
    ``resume_templates["pop_template"]`` as the unpadded population
    template and optional ``resume_templates["overrides"]`` replacing
    trimmed checkpoint-leaf templates, e.g. shard-trimmed event state);
    ``save_state(carry)`` maps a live carry to the (engine-portable)
    checkpoint state dict. ``meta``/``history_fn`` default to the
    synchronous family; ``capture``, when a dict, receives the full
    concatenated trajectory under ``"traj"`` (parity-test hook). The
    segments run in the span ``fl.scan``, ``history_fn`` in
    ``fl.history``."""
    if meta is None:
        meta = _train_meta(cfg, "train-sync")
    if history_fn is None:
        history_fn = _history_from_traj
    ck = _make_checkpointer(cfg.checkpoint_path, cfg.checkpoint_every,
                            cfg.rounds, meta)
    parts: List[Dict[str, Any]] = []
    if cfg.resume_from:
        templates = dict(zip(carry_names, carry0))
        templates["pop"] = resume_templates["pop_template"]
        templates.update(resume_templates.get("overrides", {}))
        with setup_transfers():  # checkpoint leaves move host->device
            start, state, saved, _ = load_engine_checkpoint(
                cfg.resume_from, templates, expect_meta=meta)
            carry = resume_templates["restore"](state)
        parts.append(saved["traj"])
        init_acc = float(saved["init_acc"])
    else:
        start = 0
        carry = carry0
    with spans.span("fl.scan"):
        for a, b in segment_bounds(start, cfg.rounds,
                                   ck.every if ck else None):
            carry, traj = run(_fused_do_eval(cfg, a, b), carry, *run_args)
            parts.append(jax.device_get(traj))
            if ck and ck.due(b):
                ck.save(b, save_state(carry),
                        {"traj": _concat_traj(parts), "init_acc": init_acc})
    traj = _concat_traj(parts)
    if capture is not None:
        capture["traj"] = traj
    with spans.span("fl.history"):
        return history_fn(cfg, init_acc, traj)


def run_fl_scanned(cfg: FLConfig, verbose: bool = False) -> FLHistory:
    """:func:`run_fl`, fully device-resident: all ``cfg.rounds`` rounds of
    REAL training run inside one jitted ``lax.scan`` (selection → energy
    simulation → masked cohort local SGD → compressed aggregation → server
    update → eval), with zero per-round host transfers. Trajectory parity
    with the host loop is the contract — see the module comment above
    :func:`_fused_runner` and ``tests/test_training_engines.py``.

    Elastic knobs (``cfg.checkpoint_path`` / ``cfg.checkpoint_every`` /
    ``cfg.resume_from``) split the scan into checkpoint-aligned segments;
    because the RNG chain rides in the scan carry, the segmented (and the
    resumed) trajectory is bitwise-identical to the uninterrupted one."""
    _reject_async_knobs(cfg, "run_fl_scanned")
    # one-time host->device materialization
    with setup_transfers(), spans.span("fl.setup"):
        (kloop, data, test, params, opt_state, pop, sim_steps, up_bytes,
         energy_model, model_bytes) = _fused_setup(cfg)
        with spans.span("fl.setup.cost_table"):
            t_total, cost = round_cost_table(pop, energy_model, model_bytes,
                                             sim_steps, cfg.batch_size,
                                             up_bytes)
        with spans.span("fl.setup.runner"):
            run, evaluate = _fused_runner(cfg.model, *_fused_statics(cfg),
                                          _auto_pallas(cfg.n_clients, None),
                                          jax.default_backend() != "tpu")
            st = SelectorState.create(cfg.selector).canonical()
        acc0, init_acc = _untrained_acc(evaluate, params, test)
        carry0 = (params, opt_state, pop, st, kloop, acc0,
                  BudgetLedger.create())
    hist = _run_fused_elastic(
        cfg, run, carry0, init_acc,
        (data["x"], data["y"], test["x"], test["y"], t_total, cost),
        {"pop_template": pop,
         "restore": lambda state: tuple(state[k] for k in _TRAIN_CARRY)},
        lambda carry: dict(zip(_TRAIN_CARRY, carry)))
    if verbose:
        _print_fused_history(cfg, hist)
    return hist


# ---------------------------------------------------- sharded training twin
# run_fl_scanned over the 1-D `clients` mesh the selection tournament lives
# on. Per round, inside one shard_map body:
#   selection+simulation run shard-local (`simulation._shard_round_step`,
#   index-for-index identical to the single-device step), the cohort's
#   per-slot training data is reassembled with one-owner-per-slot psum
#   gathers, and the slot axis is then split EVENLY across shards — each
#   shard runs local SGD for n_slots/S slots (true data parallelism over
#   the cohort) and contributes its partial weighted delta via a psum.
# The server update + eval run on replicated params in the outer scan body.
#
# Parity contract vs run_fl_scanned: selection indices, success masks and
# battery/dropout trajectories are index-for-index / bitwise identical
# (same rank-bit streams, same elementwise battery math, exactly
# associative pmax durations); the aggregated delta differs in the last
# ulp (psum of per-shard partial tensordots reorders the weighted
# reduction), so params — and everything downstream (acc/loss/stat-util)
# — match within float tolerance rather than bitwise
# (`launch/sharded_check.py --train`).


@functools.lru_cache(maxsize=4)
def _sharded_fused_runner(model_cfg: ResNetConfig, sel_cfg: SelectorConfig,
                          agg_k: int, energy_model: EnergyModel,
                          deadline_s: Optional[float],
                          local_steps: int, batch_size: int,
                          client_lr: float, fedprox_mu: float,
                          compression: str, sparsity: float,
                          server_opt: str, server_lr: float,
                          recharge_pct_per_hour: float, plugged_frac: float,
                          rejoin_pct: float, faults: Optional[FaultConfig],
                          energy_budget_j: Optional[float],
                          use_pallas: bool,
                          interpret: bool, mesh, n_real: int,
                          axis_name: str):
    """Cached jitted sharded fused training scan (statics mirror
    :func:`_fused_runner` plus the mesh geometry). Returns the same
    segment-callable ``(run, evaluate)`` pair as :func:`_fused_runner`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    opt = make_server_optimizer(server_opt, server_lr)
    cohort = _cohort_train_fn(model_cfg, local_steps, batch_size, client_lr,
                              fedprox_mu, compression, sparsity)
    faulty = faults is not None and faults.active
    n_shards = mesh.shape[axis_name]
    n_padded = n_real + (-n_real) % n_shards
    n_slots = min(sel_cfg.k, n_real)
    pad_s = (-n_slots) % n_shards
    n_slots_pad = n_slots + pad_s
    n_per = n_slots_pad // n_shards
    spec, rep = P(axis_name), P()

    def _pad_slots(a, fill=0):
        if pad_s == 0:
            return a
        return jnp.concatenate(
            [a, jnp.full((pad_s,) + a.shape[1:], fill, a.dtype)])

    def body(ksel, ktrain, st, params, pop, ledger, x_loc, y_loc, t_total,
             cost, bits, u_rech, *fstreams):
        n_loc = cost.shape[0]
        shard_i = jax.lax.axis_index(axis_name)
        base = (shard_i * n_loc).astype(jnp.int32)
        streams = fstreams[0] if faulty else None
        # the ledger always rides along (accounting runs unmetered too);
        # the gate inside _shard_round_step psums the predicted cohort
        # joules, so admit/refuse is a replicated decision across shards
        (pop, st, idx, chosen, slot_succ, dev, retries, corrupt_sel,
         _admit, ledger) = _shard_round_step(
            ksel, st, pop, t_total, cost, bits, sel_cfg=sel_cfg,
            energy_model=energy_model, deadline_s=deadline_s,
            use_pallas=use_pallas, interpret=interpret,
            axis_name=axis_name, n_real=n_real,
            faults=faults if faulty else None, streams=streams,
            energy_budget_j=energy_budget_j, ledger=ledger)
        if n_slots > agg_k:
            if faulty:
                # the straggler cap ranks on the fault-modified durations
                # (elementwise recompute of the same deterministic draw
                # _shard_round_step applied — bitwise identical)
                t_cap, _, _ = apply_faults(
                    faults, t_total, cost,
                    tuple(streams[:, j] for j in range(N_FAULT_STREAMS)))
            else:
                t_cap = t_total
            slot_dur = _slot_gather(t_cap, idx, chosen, base, axis_name)
            g = jnp.where(slot_succ, -slot_dur, -jnp.inf)
            _, keep_slots = jax.lax.top_k(g, agg_k)
            keep = jnp.zeros((n_slots,), bool).at[keep_slots].set(True)
            mask = slot_succ & keep
        else:
            mask = slot_succ
        if recharge_pct_per_hour > 0.0:
            # pre-generated sharded uniform stream (prefix-stable: the
            # first n_real draws equal the single-device bernoulli's);
            # pad clients are masked out so they can never recharge-rejoin
            real = (base + jnp.arange(n_loc)) < n_real
            plugged = (u_rech < plugged_frac) & real
            gain = recharge_pct_per_hour * dev.round_duration / 3600.0
            battery = jnp.clip(pop.battery_pct + plugged * gain, 0.0, 100.0)
            rejoin = pop.dropped & (battery >= rejoin_pct)
            pop = pop.replace(battery_pct=battery,
                              dropped=pop.dropped & ~rejoin)
        # --- cohort gather: one shard owns each slot's client ------------
        own = (idx >= base) & (idx < base + n_loc)
        loc = jnp.clip(idx - base, 0, n_loc - 1)

        def gather_data(a_loc):
            shape = (own.shape[0],) + (1,) * (a_loc.ndim - 1)
            vals = jnp.where(own.reshape(shape), a_loc[loc],
                             jnp.zeros((), a_loc.dtype))
            return jax.lax.psum(vals, axis_name)

        xg = _pad_slots(gather_data(x_loc))          # (n_slots_pad, M, ...)
        yg = _pad_slots(gather_data(y_loc))
        wg = _slot_gather(pop.n_samples, idx, mask, base, axis_name)
        ranks = jnp.clip(jnp.cumsum(mask) - 1, 0, n_slots - 1)
        keys = _pad_slots(jax.random.split(ktrain, n_slots)[ranks])
        # --- even slot split: shard i trains slots [i*n_per, (i+1)*n_per)
        sl = shard_i * n_per
        x_sl = jax.lax.dynamic_slice_in_dim(xg, sl, n_per)
        y_sl = jax.lax.dynamic_slice_in_dim(yg, sl, n_per)
        k_sl = jax.lax.dynamic_slice_in_dim(keys, sl, n_per)
        deltas, per_sample, mean_losses = cohort(params, x_sl, y_sl, k_sl)
        if faulty:
            # corrupted-upload fault on this shard's slot slice
            bad_sl = jax.lax.dynamic_slice_in_dim(
                _pad_slots(corrupt_sel & mask), sl, n_per)
            deltas = jax.tree.map(
                lambda d: jnp.where(
                    bad_sl.reshape((n_per,) + (1,) * (d.ndim - 1)),
                    jnp.nan, d), deltas)
        # non-finite quarantine (always on): per-shard finite mask over the
        # local slot slice, all_gathered back into slot order; quarantined
        # slots lose their weight AND their delta row (0 * nan == nan), so
        # the psum-merged weighted mean renormalizes over the survivors —
        # this is also what degrades gracefully when a whole shard's slots
        # go bad: the global weight sum shrinks to the surviving shards
        fin_sl = finite_rows(deltas)
        deltas = zero_nonfinite_rows(deltas, fin_sl)
        fin = jax.lax.all_gather(fin_sl, axis_name).reshape(-1)[:n_slots]
        good = mask & fin
        wq = jnp.where(fin, wg, jnp.zeros((), wg.dtype))
        wq_p = _pad_slots(wq)
        w_sl = jax.lax.dynamic_slice_in_dim(wq_p, sl, n_per)
        # partial weighted delta: normalize by the GLOBAL surviving weight
        # sum, then psum the per-shard partial tensordots (weighted_delta's
        # math, reduction split across shards)
        wn = wq_p / jnp.maximum(jnp.sum(wq), 1e-9)
        wn_sl = jax.lax.dynamic_slice_in_dim(wn, sl, n_per)
        agg = jax.tree.map(
            lambda d: jax.lax.psum(
                weighted_sum(wn_sl, d), axis_name),
            deltas)
        # replicated per-slot stats (all_gather in shard order == slot order)
        su = jax.lax.all_gather(
            stat_utility(per_sample, w_sl), axis_name).reshape(-1)
        losses = jax.lax.all_gather(mean_losses, axis_name).reshape(-1)
        good_p = _pad_slots(good)
        own_p = _pad_slots(own)
        loc_p = _pad_slots(loc)
        pop = scatter_stat_util(pop, loc_p, good_p & own_p, su)
        ts = pop.times_selected.astype(jnp.float32)
        s1 = jax.lax.psum(jnp.sum(ts), axis_name)
        s2 = jax.lax.psum(jnp.sum(jnp.square(ts)), axis_name)
        stats = {
            "selected": idx,
            "chosen": chosen,
            "succeeded": mask,
            "round_duration": dev.round_duration,
            "new_dropouts": dev.new_dropouts,
            "energy_spent_pct": dev.energy_spent_pct,
            "mean_battery": (jax.lax.psum(jnp.sum(pop.battery_pct),
                                          axis_name) / n_real),
            "fairness": jnp.where(s2 > 0,
                                  jnp.square(s1) / (n_real * s2), 1.0),
            "any_good": good.any(),
            "retries": retries,
            "quarantined": jnp.sum(mask & ~fin).astype(jnp.int32),
            # masked per-slot losses; train_loss is reduced host-side over
            # the compacted slots (see _fused_runner / _history_from_traj)
            "slot_losses": jnp.where(mask, losses[:n_slots], 0.0),
            "energy_spent_j": ledger.spent_j,
            "budget_exhausted": ledger.exhausted_round,
        }
        return pop, st, agg, stats, ledger

    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, rep, spec, rep, spec, spec, spec, spec,
                  spec, spec) + ((spec,) if faulty else ()),
        out_specs=(spec, rep, rep, rep, rep), check_vma=False)

    @jax.jit
    def evaluate(params, test_x, test_y):
        return _test_accuracy(model_cfg, params, test_x, test_y)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(do_eval, carry, data_x, data_y, test_x, test_y, t_total, cost):
        def eval_acc(p):
            return _test_accuracy(model_cfg, p, test_x, test_y)

        shard = NamedSharding(mesh, spec)

        def scan_step(carry, do_eval):
            params, opt_state, pop, st, kloop, last_acc, ledger = carry
            kloop, ksel, ktrain, krecharge = jax.random.split(kloop, 4)
            # prefix-stable sharded streams: rank bits for selection, a
            # uniform stream for the recharge bernoulli (u < p)
            bits = jax.lax.with_sharding_constraint(
                _rank_bits(ksel, n_padded), shard)
            kplug = jax.random.fold_in(krecharge, 7)
            u_rech = jax.lax.with_sharding_constraint(
                jax.random.uniform(kplug, (n_padded,)), shard)
            fargs = ()
            if faulty:
                # global fault streams for post-select round st.round + 1,
                # generated OUTSIDE the shard_map (prefix-stable threefry:
                # each shard slices its rows of the one global stream)
                fargs = (jax.lax.with_sharding_constraint(
                    jnp.stack(fault_streams(faults, st.round + 1, n_padded),
                              axis=-1), shard),)
            pop, st, agg, stats, ledger = smapped(
                ksel, ktrain, st, params, pop, ledger, data_x, data_y,
                t_total, cost, bits, u_rech, *fargs)
            new_params, new_opt = server_update(params, agg, opt, opt_state)
            # last-resort aggregate gate, like the single-device engine
            ok = stats.pop("any_good") & tree_finite(agg)
            params = jax.tree.map(
                lambda a, b: jnp.where(ok, a, b), new_params, params)
            opt_state = jax.tree.map(
                lambda a, b: jnp.where(ok, a, b), new_opt, opt_state)
            last_acc = jax.lax.cond(do_eval, eval_acc,
                                    lambda _: last_acc, params)
            out = dict(stats, test_acc=last_acc,
                       update_skipped=(~ok).astype(jnp.int32))
            return (params, opt_state, pop, st, kloop, last_acc,
                    ledger), out

        return jax.lax.scan(scan_step, carry, do_eval)

    return run, evaluate


def run_fl_sharded(cfg: FLConfig, verbose: bool = False, mesh=None,
                   n_shards: Optional[int] = None) -> FLHistory:
    """:func:`run_fl_scanned` on the `clients` mesh: population, data and
    simulation shard-resident, cohort local SGD data-parallel across
    shards, weighted deltas psum-merged. Defaults to a mesh over all
    visible devices (virtual CPU devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count``)."""
    from repro.launch.mesh import make_client_mesh
    from repro.launch.sharding import population_sharding

    _reject_async_knobs(cfg, "run_fl_sharded")
    if mesh is None:
        mesh = make_client_mesh(n_shards)
    axis_name = mesh.axis_names[0]
    # one-time host->device materialization
    with setup_transfers(), spans.span("fl.setup"):
        (kloop, data, test, params, opt_state, pop, sim_steps, up_bytes,
         energy_model, model_bytes) = _fused_setup(cfg)
        n_real = pop.n
        pop0 = pop  # unpadded host population — the checkpoint template
        sharding = population_sharding(mesh, axis_name)
        pop = jax.device_put(pad_population(pop, mesh.shape[axis_name]),
                             sharding)
        pad = pop.n - n_real

        def pad_clients(a):
            if pad:
                a = jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
            return jax.device_put(a, sharding)

        data_x, data_y = pad_clients(data["x"]), pad_clients(data["y"])
        with spans.span("fl.setup.cost_table"):
            t_total, cost = round_cost_table(pop, energy_model, model_bytes,
                                             sim_steps, cfg.batch_size,
                                             up_bytes, sharding=sharding)
        with spans.span("fl.setup.runner"):
            run, evaluate = _sharded_fused_runner(
                cfg.model, *_fused_statics(cfg), _auto_pallas(n_real, None),
                jax.default_backend() != "tpu", mesh, n_real, axis_name)
            st = SelectorState.create(cfg.selector).canonical()
        acc0, init_acc = _untrained_acc(evaluate, params, test)
        carry0 = (params, opt_state, pop, st, kloop, acc0,
                  BudgetLedger.create())

    # the checkpoint stores the population TRIMMED to the real clients (the
    # pad tail is provably inert: dead, never selected, never recharged),
    # which makes "train-sync" snapshots portable across device counts AND
    # across the scanned/sharded engines
    def _restore(state):
        rpop = jax.device_put(
            pad_population(state["pop"], mesh.shape[axis_name]), sharding)
        return (state["params"], state["opt_state"], rpop, state["st"],
                state["kloop"], state["last_acc"], state["ledger"])

    def _save_state(carry):
        s = dict(zip(_TRAIN_CARRY, carry))
        s["pop"] = jax.tree.map(lambda x: x[:n_real], s["pop"])
        return s

    hist = _run_fused_elastic(
        cfg, run, carry0, init_acc,
        (data_x, data_y, test["x"], test["y"], t_total, cost),
        {"pop_template": pop0, "restore": _restore},
        _save_state)
    if verbose:
        _print_fused_history(cfg, hist)
    return hist


def run_selection_scanned(cfg: FLConfig, rounds: Optional[int] = None,
                          use_pallas: Optional[bool] = None,
                          n_shards: Optional[int] = None,
                          mesh=None, mode: str = "auto",
                          ) -> Tuple[ClientPopulation, Dict[str, Any]]:
    """The device-resident fast path: selection + energy + battery advanced
    for ``rounds`` rounds inside one ``jax.lax.scan`` (no training — the
    trajectory's per-round ``selected`` indices are the interface for
    dispatching training separately).

    Uses the same population, energy model, and simulated device workload
    as :func:`run_fl`, so its battery/dropout trajectories match the host
    loop within float tolerance. Dispatch goes through the unified
    :func:`repro.federated.run_rounds` front door: ``mode`` (default
    ``"auto"``) plus ``cfg``'s async knobs and the population size pick
    among the scanned / sharded / async engines (``n_shards``/``mesh``
    force the sharded variant); the selection trajectory is
    index-identical whichever engine runs, and the engine actually chosen
    is reported in the returned dict's ``"engine"`` key.
    """
    key = jax.random.PRNGKey(cfg.seed)
    kpop, _kdata, kmodel, _ktest, kloop = jax.random.split(key, 5)
    if cfg.sim_model_bytes is not None:
        model_bytes = cfg.sim_model_bytes
    else:
        params = init_resnet(kmodel, cfg.model)
        model_bytes = sum(x.size for x in jax.tree.leaves(params)) * 4.0
    pop, sim_steps, up_bytes, energy_model = _engine_setup(cfg, kpop,
                                                           model_bytes)
    final_pop, final_state, traj = run_rounds(
        kloop, cfg.selector, pop, SelectorState.create(cfg.selector),
        energy_model, model_bytes, sim_steps, cfg.batch_size,
        rounds if rounds is not None else cfg.rounds, mode=mode, deadline_s=cfg.deadline_s,
        up_bytes=up_bytes, use_pallas=use_pallas,
        buffer_size=cfg.buffer_size, max_concurrency=cfg.max_concurrency,
        staleness_power=cfg.staleness_power, mesh=mesh, n_shards=n_shards,
        faults=cfg.faults, checkpoint_every=cfg.checkpoint_every,
        checkpoint_path=cfg.checkpoint_path, resume_from=cfg.resume_from)
    return final_pop, {"state": final_state, **traj}
