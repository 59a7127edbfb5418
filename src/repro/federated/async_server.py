"""FedBuff-style buffered-asynchronous FL server (the async twin of
:func:`repro.federated.server.run_fl`).

EAFL's central failure mode is the synchronous barrier: every selected
client must finish before aggregation, so stragglers stretch
time-to-accuracy and drained devices are abandoned at the deadline. Here
each client trains on its own clock (the device-resident event core in
:mod:`repro.federated.simulation`): the server aggregates whenever
``buffer_size`` updates have arrived, damps each delta by
``1/(1+staleness)**staleness_power`` (FedBuff, Nguyen et al. AISTATS'22),
and immediately refills the freed concurrency slots, so slow or low-energy
clients contribute late instead of never.

Training is REAL and staleness is physical: every cohort member trains
from the parameter version it actually downloaded, and its delta is
applied to the *current* parameters as a damped pseudo-gradient. Three
engines share one trajectory contract:

- :func:`run_fl_async` — the host reference loop. One ``engine_step``
  call per aggregation, training dispatched host-side. This is the
  acceptance oracle for the fused engines.
- :func:`run_fl_async_scanned` — the whole event step (flush → canonical
  reorder → stale-start cohort SGD → damped aggregation → server update →
  refill) folded into one jitted ``lax.scan``. Parameter versions live in
  a fixed-size in-carry snapshot ring (:class:`SnapshotRingState`):
  stacked params + version ids + refcounts riding the scan carry, so the
  server params can be donated — the ring owns every version a stale
  client can still request.
- :func:`run_fl_async_sharded` — the scanned engine over the 1-D
  `clients` mesh (population/data/event state sharded, ring replicated,
  cohort SGD data-parallel over the flush axis).

Parity contract: host and scanned runs produce identical flush / refill /
version trajectories index-for-index and stats to engine precision; in
the ``buffer_size == max_concurrency == k``, ``staleness_power == 0``
limit the async engines reproduce the *sync* ``run_fl_scanned``
trajectory (see ``tests/test_async_training_engines.py``).

RNG contract (shared by all three engines, and the thing that makes the
sync-limit bitwise): every aggregation — and the initial fill — burns one
``kloop, ksel, ktrain, krecharge = split(kloop, 4)`` exactly like a sync
round. The fill's ``ksel`` primes the pipe (sync round 1's selection);
aggregation ``r``'s ``ksel`` drives the refill (sync round ``r+1``'s
selection). Training keys are *version-anchored*: the ``ktrain`` of the
split that created parameter version ``v`` is stored in the ring slot,
and a completer that downloaded ``v`` trains with
``split(tkey_v, max_concurrency)[succ_v + rank]`` where ``succ_v`` counts
earlier successful completers of ``v`` and ``rank`` is the completer's
success rank within the flush — in the sync limit this is exactly the
sync engine's success-rank key assignment. Recharge uses the *previous*
split's ``krecharge`` (the fill's for aggregation 1), which again lines
up with the sync rounds.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.analysis.runtime import setup_transfers
from repro.checkpoint import load_engine_checkpoint
from repro.core import SelectorState, jains_index, stat_utility
from repro.core.clients import pad_population, scatter_stat_util
from repro.core.selection import _auto_pallas, _rank_bits, _slot_gather
from repro.federated.aggregation import (
    finite_rows,
    make_server_optimizer,
    server_update,
    tree_finite,
    weighted_delta,
    weighted_sum,
    zero_nonfinite_rows,
)
from repro.federated.server import (
    FLConfig,
    FLHistory,
    _cohort_train_fn,
    _fused_do_eval,
    _fused_setup,
    _local_train_fn,
    _print_fused_history,
    _recharge_step,
    _record_test_acc,
    _run_fused_elastic,
    _test_accuracy,
    _train_meta,
    _untrained_acc,
)
from repro.federated.simulation import (
    AsyncEventState,
    _asum,
    _async_knobs,
    _make_checkpointer,
    _pad_astate,
    _shard_async_fill,
    _shard_async_step,
    _slot_gather_i32,
    make_async_round_engine,
    round_cost_table,
)

_I32_MAX = np.iinfo(np.int32).max


class _SnapshotRing:
    """Host-side refcounted parameter versions (dict-backed).

    Kept as the *executable specification* for the in-carry
    :class:`SnapshotRingState`: the hypothesis fuzz in
    ``tests/test_snapshot_ring.py`` drives random retain/release traffic
    through both and cross-checks live versions and refcounts. The
    training engines themselves all use the array ring now.
    """

    def __init__(self):
        self._params: Dict[int, object] = {}
        self._refs: Dict[int, int] = {}

    def retain(self, version: int, params, count: int):
        if count <= 0:
            return
        if version not in self._params:
            self._params[version] = params
        self._refs[version] = self._refs.get(version, 0) + count

    def get(self, version: int):
        return self._params[version]

    def release(self, version: int):
        self._refs[version] -= 1
        if self._refs[version] == 0:
            del self._refs[version]
            del self._params[version]

    @property
    def live_versions(self) -> int:
        return len(self._params)


# --------------------------------------------------- in-carry snapshot ring
# A fixed-size array twin of _SnapshotRing that can ride a lax.scan carry:
# `size` slots of stacked parameters plus (version, refcount, train-key,
# success-count) bookkeeping rows. Free slots have version == -1.
#
# Capacity argument (why `size = max_concurrency` suffices): every live
# version is held by >= 1 in-flight client and there are never more than
# max_concurrency in-flight clients (the flush frees min(B, n_if) slots
# and the refill adds <= B), so live_versions <= max_concurrency <= size
# and a retain with count > 0 always finds a free slot — versions are
# monotone and a version with zero holders has been freed, so retain
# never needs to top up an existing slot.


class SnapshotRingState(NamedTuple):
    """``size`` parameter-version slots riding a scan carry.

    ``params`` stacks every model leaf along a new leading ``size`` axis;
    ``version`` is -1 for free slots; ``refs`` counts in-flight holders;
    ``tkey`` is the raw (2,) uint32 train key of the split that created
    the version; ``succ`` counts completers of this version that already
    trained successfully (the base of the success-rank key index).
    """

    params: Any                # pytree, each leaf (size, ...)
    version: jnp.ndarray       # (size,) i32, -1 == free
    refs: jnp.ndarray          # (size,) i32
    tkey: jnp.ndarray          # (size, 2) u32
    succ: jnp.ndarray          # (size,) i32

    @property
    def live_versions(self) -> jnp.ndarray:
        return jnp.sum(self.version >= 0).astype(jnp.int32)


def _ring_create(params, size: int) -> SnapshotRingState:
    """An all-free ring whose param slots broadcast ``params`` (any value
    works — free slots are never read through a version match)."""
    stacked = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (size,) + p.shape), params)
    return SnapshotRingState(
        params=stacked,
        version=jnp.full((size,), -1, jnp.int32),
        refs=jnp.zeros((size,), jnp.int32),
        tkey=jnp.zeros((size, 2), jnp.uint32),
        succ=jnp.zeros((size,), jnp.int32))


def _ring_lookup(ring: SnapshotRingState, versions) -> jnp.ndarray:
    """Slot index per requested version. A non-live version (masked rows
    ask for _I32_MAX) falls back to slot 0 — harmless, the caller's
    weight/success masks zero those rows out of everything downstream."""
    return jnp.argmax(ring.version[None, :] == versions[:, None],
                      axis=1).astype(jnp.int32)


def _ring_release(ring: SnapshotRingState, versions, chosen,
                  succ) -> SnapshotRingState:
    """Release one reference per chosen flush row (its ``versions`` entry)
    and bank each successful completer into its version's ``succ`` base.
    Slots whose refcount reaches zero are freed (version := -1)."""
    member = (ring.version[:, None] == versions[None, :]) & chosen[None, :]
    released = jnp.sum(member, axis=1).astype(jnp.int32)
    succ_add = jnp.sum(member & succ[None, :], axis=1).astype(jnp.int32)
    refs = ring.refs - released
    freed = (released > 0) & (refs <= 0)
    return ring._replace(
        version=jnp.where(freed, jnp.int32(-1), ring.version),
        refs=jnp.maximum(refs, 0),
        succ=ring.succ + succ_add)


def _ring_retain(ring: SnapshotRingState, version, params, count,
                 tkey) -> SnapshotRingState:
    """Claim a free slot for ``count`` new holders of ``version`` (a
    no-op when ``count == 0``). ``version`` is always fresh here: a
    version with zero holders has been freed, and refills only ever start
    clients on the current server version (see capacity argument above)."""
    size = ring.version.shape[0]
    slot = jnp.argmax(ring.version < 0).astype(jnp.int32)
    ok = (jnp.asarray(count) > 0) & (ring.version[slot] < 0)
    tgt = jnp.where(ok, slot, size)
    return SnapshotRingState(
        params=jax.tree.map(
            lambda r, p: r.at[tgt].set(p, mode="drop"), ring.params, params),
        version=ring.version.at[tgt].set(
            jnp.asarray(version, jnp.int32), mode="drop"),
        refs=ring.refs.at[tgt].set(
            jnp.asarray(count, jnp.int32), mode="drop"),
        tkey=ring.tkey.at[tgt].set(tkey, mode="drop"),
        succ=ring.succ.at[tgt].set(0, mode="drop"))


def _within_version_rank(versions, succ) -> jnp.ndarray:
    """Per-row success rank *within its parameter version*, over the
    canonically ordered flush: ``out[i] = #{j < i: v_j == v_i and
    succ_j}``. O(B^2) on the tiny flush axis."""
    b = versions.shape[0]
    same = versions[None, :] == versions[:, None]
    earlier = jnp.tril(jnp.ones((b, b), bool), k=-1)
    return jnp.sum(same & earlier & succ[None, :], axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("width",))
def _flush_train_keys(tkeys, key_ix, width: int):
    """Per-row train key: ``split(tkeys[i], width)[key_ix[i]]``. The split
    is partitionable threefry, so ``width`` (= max_concurrency) being
    static while ``key_ix`` is data keeps every row's key equal to the
    host loop's dynamic ``split``."""
    return jax.vmap(lambda tk, i: jax.random.split(tk, width)[i])(tkeys,
                                                                  key_ix)


# host-loop facades (one trace each — shapes are round-invariant)
_ring_release_jit = jax.jit(_ring_release)
_ring_retain_jit = jax.jit(_ring_retain)


def _check_async_cfg(cfg: FLConfig) -> None:
    """The async engines' structural-knob rejections (shared by all three
    engines so the error surface cannot drift)."""
    if cfg.overcommit != 1.0:
        raise ValueError("overcommit is a synchronous-barrier knob; the "
                         "async engine refills slots continuously instead")
    if cfg.faults is not None and cfg.faults.active:
        raise ValueError(
            "fault injection is defined per synchronous round; the async "
            "event engine has no per-round fault boundary — run faults "
            "through run_fl(mode='sync') / the sync round engines")
    if cfg.controller is not None:
        raise ValueError(
            "the adaptive knob controller drives the synchronous host "
            "loop; the async event engine's knobs (buffer_size, "
            "max_concurrency) are structural — use run_fl(cfg, "
            "mode='sync', engine='host')")


def _async_geometry(cfg: FLConfig):
    """``(buffer_size, max_concurrency, snapshot_ring_size)`` normalized
    the way every async engine sees them."""
    b, c, _, _ = _async_knobs(cfg.selector, cfg.buffer_size,
                              cfg.max_concurrency)
    r = c if cfg.snapshot_ring_size is None else int(cfg.snapshot_ring_size)
    if r < c:
        raise ValueError(
            "snapshot_ring_size must be >= max_concurrency "
            f"({r} < {c}): every in-flight client can in the worst case "
            "hold a distinct parameter version")
    return b, c, r


def _async_train_meta(cfg: FLConfig, family: str) -> Dict[str, Any]:
    """Checkpoint identity for the async training engines: the sync
    training meta plus the normalized FedBuff geometry (normalized so a
    run with explicit ``buffer_size=k`` and one with the default resolve
    to the same identity — they are the same trajectory)."""
    b, c, r = _async_geometry(cfg)
    meta = _train_meta(cfg, family)
    meta.update(buffer_size=b, max_concurrency=c,
                staleness_power=float(cfg.staleness_power),
                snapshot_ring_size=r)
    return meta


# ------------------------------------------------------ host reference loop
# Per-aggregation flow (identical, op-for-op, to the scanned engine's scan
# body — the host/NumPy work is only ordering and bookkeeping):
#   split(kloop, 4) -> engine_step(ksel) flush+refill -> canonical reorder
#   (sort flush rows by (start version, selection-slot rank); masked rows
#   last) -> recharge with the PREVIOUS split's krecharge -> per-row start
#   params + train keys from the snapshot ring -> cohort SGD (compacted to
#   the successful rows; the scan trains the full masked width, which the
#   zero-weight aggregation makes bitwise-equivalent) -> quarantine +
#   damped weighted aggregation -> gated server update -> ring release
#   (flushed holders) + retain (refilled holders on the new version) ->
#   selection-rank bookkeeping for the refill batch.


def run_fl_async(cfg: FLConfig, verbose: bool = False,
                 _trace: Optional[list] = None) -> FLHistory:
    """Buffered-asynchronous FL: ``cfg.rounds`` server aggregations.

    Reached via ``run_fl(cfg, mode="async", engine="host")`` — the
    dispatcher's default async engine is :func:`run_fl_async_scanned`
    (or the sharded twin on multi-device hosts); this host loop is the
    parity oracle the fused engines are tested against.

    One history row per aggregation (``round_duration`` is the wall time
    between consecutive aggregations, so ``wall_hours`` is directly
    comparable with the sync loop's). ``cfg.buffer_size`` /
    ``cfg.max_concurrency`` default to ``selector.k`` — the sync-parity
    regime — and ``cfg.staleness_power`` damps stale deltas.

    ``_trace`` (tests only): a list that receives one dict per
    aggregation with the canonical-order flush/refill columns, the
    index-for-index parity surface for the fused engines.
    """
    _check_async_cfg(cfg)
    buffer_size, max_concurrency, ring_size = _async_geometry(cfg)
    with spans.span("fl.setup"):
        (kloop, data, test, params, opt_state, pop, sim_steps, up_bytes,
         energy_model, model_bytes) = _fused_setup(cfg)
    opt = make_server_optimizer(cfg.server_opt, cfg.server_lr)
    sel_state = SelectorState.create(cfg.selector).canonical()
    astate = AsyncEventState.create(pop.n)
    n = pop.n
    # per-client start params (params_axis=0): each completer trains from
    # the version it actually downloaded, so staleness is real
    local_train = _local_train_fn(cfg.model, cfg.local_steps,
                                  cfg.batch_size, cfg.client_lr,
                                  cfg.fedprox_mu, cfg.compression,
                                  cfg.compression_sparsity, params_axis=0)

    init_fill, engine_step = make_async_round_engine(
        cfg.selector, energy_model, model_bytes, sim_steps, cfg.batch_size,
        buffer_size=cfg.buffer_size, max_concurrency=cfg.max_concurrency,
        staleness_power=cfg.staleness_power, deadline_s=cfg.deadline_s,
        up_bytes=up_bytes, energy_budget_j=cfg.energy_budget_j)
    init_fill = jax.jit(init_fill)
    # pop / sel_state / astate are dead after each step (the loop rebinds
    # them), so donate their buffers instead of holding two copies
    engine_step = jax.jit(engine_step, donate_argnums=(1, 2, 3))

    # params ARE donatable now: the snapshot ring owns every version an
    # in-flight stale client can still request (retain copies the leaves
    # into the ring slots), so the server copy is free to be overwritten
    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def server_step(p, agg_delta, o_state):
        return server_update(p, agg_delta, opt, o_state)

    @jax.jit
    def test_acc_fn(p):
        return _test_accuracy(cfg.model, p, test["x"], test["y"])

    meta = _async_train_meta(cfg, "train-async-host")
    ck = _make_checkpointer(cfg.checkpoint_path, cfg.checkpoint_every,
                            cfg.rounds, meta)
    start = 0
    if cfg.resume_from:
        # plain carry restore — the ring is an ordinary fixed-shape carry
        # rider now, no two-phase per-version reload
        templates = {"params": params, "opt_state": opt_state, "pop": pop,
                     "st": sel_state, "astate": astate,
                     "ring": _ring_create(params, ring_size),
                     "slot_rank": jnp.zeros((n,), jnp.int32),
                     "krech": kloop, "kloop": kloop}
        with setup_transfers():
            start, state, saved, _ = load_engine_checkpoint(
                cfg.resume_from, templates, expect_meta=meta)
        params, opt_state, pop = (state["params"], state["opt_state"],
                                  state["pop"])
        sel_state, astate, ring = state["st"], state["astate"], state["ring"]
        krech, kloop = state["krech"], state["kloop"]
        slot_rank_np = np.asarray(state["slot_rank"]).copy()
        hist = FLHistory(**saved["hist"])
        cum_drop = int(saved["cum_drop"])
        last_loss = float(saved["last_loss"])
    else:
        hist = FLHistory()
        hist.init_acc = float(test_acc_fn(params))
        cum_drop = 0
        last_loss = float("nan")

        # ---- prime the concurrency slots (server version 0) -------------
        kloop, ksel, ktrain, krecharge = jax.random.split(kloop, 4)
        sel_state, astate, idx0, chosen0 = init_fill(ksel, pop, sel_state,
                                                     astate)
        idx0_np, chosen0_np = np.asarray(idx0), np.asarray(chosen0)
        slot_rank_np = np.zeros((n,), np.int32)
        slot_rank_np[idx0_np[chosen0_np]] = np.where(chosen0_np)[0]
        ring = _ring_create(params, ring_size)
        ring = _ring_retain_jit(ring, jnp.int32(0), params,
                                jnp.int32(chosen0_np.sum()), ktrain)
        krech = krecharge

    for agg in range(start + 1, cfg.rounds + 1):
        kloop, ksel, ktrain, krecharge = jax.random.split(kloop, 4)
        version_before = int(astate.server_version)
        pop, sel_state, astate, flush, (ridx, rchosen) = engine_step(
            ksel, pop, sel_state, astate, jnp.bool_(True))

        chosen = np.asarray(flush["comp_chosen"])
        cidx = np.asarray(flush["completed"])
        succ_m = np.asarray(flush["succeeded"])
        stale = np.asarray(flush["staleness"])
        aggw = np.asarray(flush["agg_weight"])
        cum_drop += int(flush["new_dropouts"])
        b = cidx.shape[0]

        # canonical flush order: (start version, selection-slot rank) with
        # masked rows last. Ties are impossible — two completers of the
        # same version came from one selection batch, so their ranks
        # differ — which makes the order engine-independent.
        v_eff = np.where(chosen, version_before - stale, _I32_MAX)
        rk = np.where(chosen, slot_rank_np[cidx], np.arange(b))
        order = np.lexsort((rk, v_eff))
        cidx_s, chosen_s, succ_s = cidx[order], chosen[order], succ_m[order]
        stale_s, aggw_s, v_s = stale[order], aggw[order], v_eff[order]

        pop = _recharge_step(cfg, pop, krech,
                             float(flush["round_duration"]))
        krech = krecharge

        # version-anchored train keys (full flush width, compacted below)
        ring_v = np.asarray(ring.version)
        ring_succ = np.asarray(ring.succ)
        slots = np.argmax(ring_v[None, :] == v_s[:, None],
                          axis=1).astype(np.int32)
        within = np.zeros((b,), np.int32)
        counts: Dict[int, int] = {}
        for i in range(b):
            within[i] = counts.get(int(v_s[i]), 0)
            if succ_s[i]:
                counts[int(v_s[i])] = within[i] + 1
        key_ix = np.clip(ring_succ[slots] + within, 0, max_concurrency - 1)
        keys_full = _flush_train_keys(ring.tkey[jnp.asarray(slots)],
                                      jnp.asarray(key_ix), max_concurrency)

        pos = np.where(succ_s)[0]
        succ = cidx_s[pos]
        skipped = 1
        n_quar = 0
        if len(succ) > 0:
            start_params = jax.tree.map(lambda r: r[jnp.asarray(slots[pos])],
                                        ring.params)
            deltas, per_sample, mean_losses = local_train(
                start_params, data["x"][succ], data["y"][succ],
                keys_full[jnp.asarray(pos)])
            # FedBuff aggregation: staleness-damped, sample-weighted mean of
            # the buffered deltas applied to the CURRENT params. A buffered
            # delta that arrives non-finite (a diverged stale client) is
            # quarantined — weight AND row zeroed, so the mean renormalizes
            # over the surviving buffer entries — and the whole update is
            # skipped if nothing finite remains
            weights = (np.asarray(pop.n_samples)[succ].astype(np.float32)
                       * aggw_s[pos])
            finite = finite_rows(deltas)
            w = jnp.where(finite, jnp.asarray(weights), 0.0)
            agg_delta = weighted_delta(zero_nonfinite_rows(deltas, finite),
                                       w)
            n_quar = int(jnp.sum(~finite))
            if bool(finite.any()) and bool(tree_finite(agg_delta)):
                params, opt_state = server_step(params, agg_delta, opt_state)
                skipped = 0
            su = stat_utility(per_sample, w)
            pop = scatter_stat_util(pop, jnp.asarray(succ), finite, su)
            last_loss = float(mean_losses.mean())

        ring = _ring_release_jit(ring, jnp.asarray(v_s),
                                 jnp.asarray(chosen_s), jnp.asarray(succ_s))
        # refilled clients download the (possibly just bumped) live version
        rchosen_np, ridx_np = np.asarray(rchosen), np.asarray(ridx)
        n_refilled = int(rchosen_np.sum())
        ring = _ring_retain_jit(ring, astate.server_version, params,
                                jnp.int32(n_refilled), ktrain)
        rpos = np.where(rchosen_np)[0]
        slot_rank_np[ridx_np[rpos]] = rpos

        if _trace is not None:
            _trace.append({
                "completed": cidx_s, "comp_chosen": chosen_s,
                "succeeded": succ_s,
                "staleness": np.where(chosen_s, stale_s, 0),
                "agg_weight": aggw_s,
                "start_version": np.where(chosen_s, v_s, 0),
                "selected": ridx_np, "chosen": rchosen_np,
                "server_version": int(astate.server_version),
                "n_inflight": int(np.asarray(astate.in_flight).sum()),
            })

        hist.round.append(agg)
        hist.wall_hours.append(float(astate.server_clock) / 3600.0)
        hist.round_duration.append(float(flush["round_duration"]))
        hist.cum_dropouts.append(cum_drop)
        hist.fairness.append(float(jains_index(pop.times_selected)))
        hist.participation.append(float(succ_s[chosen_s].mean())
                                  if chosen_s.any() else 0.0)
        hist.mean_battery.append(float(pop.battery_pct.mean()))
        hist.train_loss.append(last_loss)
        hist.retries.append(0)  # transient faults are sync-engine-only
        hist.quarantined.append(n_quar)
        hist.update_skipped.append(skipped)
        # cumulative joules from the event-state ledger (charged when a
        # client's completion flushes; admission was gated against budget
        # minus in-flight commitments, so this can never exceed the budget)
        hist.energy_spent_j.append(float(astate.spent_j))
        if hist.budget_exhausted_round is None \
                and int(astate.exhausted_round) > 0:
            hist.budget_exhausted_round = int(astate.exhausted_round)
        _record_test_acc(hist, cfg, agg, params, test_acc_fn)
        if verbose and agg % 10 == 0:
            print(f"[{cfg.selector.kind}/async] agg={agg} "
                  f"acc={hist.test_acc[-1]:.3f} loss={last_loss:.3f} "
                  f"drop={cum_drop} fair={hist.fairness[-1]:.3f} "
                  f"wall={hist.wall_hours[-1]:.2f}h "
                  f"stale_max={int(stale_s.max()) if chosen_s.any() else 0}")
        if ck and ck.due(agg):
            ck.save(agg,
                    {"params": params, "opt_state": opt_state, "pop": pop,
                     "st": sel_state, "astate": astate, "ring": ring,
                     "slot_rank": jnp.asarray(slot_rank_np),
                     "krech": krech, "kloop": kloop},
                    {"hist": hist.as_dict(), "cum_drop": cum_drop,
                     "last_loss": last_loss})
        # population exhausted: nothing in flight and nothing refillable
        if not chosen_s.any() and n_refilled == 0 \
                and not bool(np.asarray(astate.in_flight).any()):
            break
    return hist


# --------------------------------------------------- fused (scanned) engine

_ASYNC_CARRY = ("params", "opt_state", "pop", "st", "astate", "ring",
                "slot_rank", "krech", "kloop", "last_acc")


def _async_history(cfg: FLConfig, init_acc: float, traj) -> FLHistory:
    """Assemble :class:`FLHistory` from an async fused trajectory.

    Differs from the sync ``_history_from_traj`` in three async-shaped
    ways: ``wall_hours`` reads the engine's f32 ``server_clock`` chain
    (exact f32->f64 widening, bitwise equal to the host loop's
    ``float(astate.server_clock)/3600``) instead of re-accumulating
    durations; ``participation`` is per-flush (succeeded / chosen);
    and the trajectory is truncated where the host loop would have
    ``break``-ed (empty flush, empty refill, nothing in flight — the
    scan keeps running inert rounds past that point).
    """
    flushed = np.asarray(traj["comp_chosen"]).sum(axis=1)
    refilled = np.asarray(traj["chosen"]).sum(axis=1)
    inflight = np.asarray(traj["n_inflight"])
    done = (flushed == 0) & (refilled == 0) & (inflight == 0)
    rows = done.shape[0]
    r_end = int(np.argmax(done)) + 1 if done.any() else rows

    hist = FLHistory(init_acc=init_acc)
    hist.round = list(range(1, r_end + 1))
    hist.wall_hours = [float(x) / 3600.0
                       for x in np.asarray(traj["server_clock"])[:r_end]]
    hist.round_duration = [float(x) for x in
                           np.asarray(traj["round_duration"])[:r_end]]
    hist.cum_dropouts = [int(x) for x in np.cumsum(
        np.asarray(traj["new_dropouts"]))[:r_end]]
    n_succ = np.asarray(traj["succeeded"]).sum(axis=1).astype(np.float64)
    hist.participation = [float(s / c) if c > 0 else 0.0
                          for s, c in zip(n_succ[:r_end],
                                          flushed[:r_end].astype(np.float64))]
    slot_losses = np.asarray(traj["slot_losses"])
    succ_mask = np.asarray(traj["succeeded"])
    last_loss = float("nan")
    hist.train_loss = []
    for r in range(r_end):
        m = succ_mask[r]
        if m.any():
            # explicit device round-trip so the f32 jnp mean — required
            # for bitwise host-loop parity — stays legal under
            # strict_mode's transfer guard
            last_loss = float(jax.device_get(
                jnp.mean(jax.device_put(slot_losses[r][m]))))
        hist.train_loss.append(last_loss)
    for name in ("test_acc", "fairness", "mean_battery"):
        setattr(hist, name, [float(x) for x in np.asarray(traj[name])[:r_end]])
    hist.retries = [0] * r_end
    for name in ("quarantined", "update_skipped"):
        setattr(hist, name, [int(x) for x in np.asarray(traj[name])[:r_end]])
    hist.energy_spent_j = [float(x) for x in
                           np.asarray(traj["energy_spent_j"])[:r_end]]
    last = int(np.asarray(traj["budget_exhausted"])[:r_end][-1])
    hist.budget_exhausted_round = last if last > 0 else None
    return hist


@functools.lru_cache(maxsize=8)
def _async_fused_runner(model_cfg, sel_cfg, energy_model,
                        deadline_s: Optional[float], sim_steps: int,
                        local_steps: int, batch_size: int, client_lr: float,
                        fedprox_mu: float, compression: str, sparsity: float,
                        server_opt: str, server_lr: float,
                        recharge_pct_per_hour: float, plugged_frac: float,
                        rejoin_pct: float, buffer_size: int,
                        max_concurrency: int, staleness_power: float,
                        ring_size: int, energy_budget_j: Optional[float],
                        model_bytes: float, up_bytes: Optional[float],
                        use_pallas: bool, interpret: bool):
    """Cached jitted fused async-training runners (hashable statics only).

    Returns ``(fill, run, evaluate)``. ``fill(kloop, params, opt_state,
    pop, st, last_acc)`` primes the concurrency slots and builds the full
    async carry (ring included). ``run(do_eval, carry, data_x, data_y,
    test_x, test_y)`` advances the carry by ``len(do_eval)`` aggregations
    — segment-callable like the sync runner, which is what makes
    checkpoint/resume restart parity bitwise.
    """
    opt = make_server_optimizer(server_opt, server_lr)
    cohort = _cohort_train_fn(model_cfg, local_steps, batch_size, client_lr,
                              fedprox_mu, compression, sparsity,
                              params_axis=0)
    init_fill, step = make_async_round_engine(
        sel_cfg, energy_model, model_bytes, sim_steps, batch_size,
        buffer_size=buffer_size, max_concurrency=max_concurrency,
        staleness_power=staleness_power, deadline_s=deadline_s,
        up_bytes=up_bytes, use_pallas=use_pallas, interpret=interpret,
        energy_budget_j=energy_budget_j)

    @jax.jit
    def evaluate(params, test_x, test_y):
        return _test_accuracy(model_cfg, params, test_x, test_y)

    @jax.jit
    def fill(kloop, params, opt_state, pop, st, last_acc):
        n = pop.n
        kloop, ksel, ktrain, krecharge = jax.random.split(kloop, 4)
        astate = AsyncEventState.create(n)
        st, astate, idx0, chosen0 = init_fill(ksel, pop, st, astate)
        slot_rank = jnp.zeros((n,), jnp.int32).at[
            jnp.where(chosen0, idx0, n)].set(
                jnp.arange(max_concurrency, dtype=jnp.int32), mode="drop")
        ring = _ring_create(params, ring_size)
        ring = _ring_retain(ring, jnp.int32(0), params,
                            jnp.sum(chosen0).astype(jnp.int32), ktrain)
        carry = (params, opt_state, pop, st, astate, ring, slot_rank,
                 krecharge, kloop, last_acc)
        return carry, idx0, chosen0

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(do_eval, carry, data_x, data_y, test_x, test_y):
        n = carry[2].n

        def eval_acc(p):
            return _test_accuracy(model_cfg, p, test_x, test_y)

        def scan_step(carry, do_eval):
            (params, opt_state, pop, st, astate, ring, slot_rank, krech,
             kloop, last_acc) = carry
            kloop, ksel, ktrain, krecharge = jax.random.split(kloop, 4)
            version_before = astate.server_version
            pop, st, astate, flush, (ridx, rchosen) = step(
                ksel, pop, st, astate, jnp.bool_(True))
            cidx, chosen = flush["completed"], flush["comp_chosen"]
            b = cidx.shape[0]
            # canonical flush order (see the host loop): stable sort on
            # (start version, selection-slot rank), masked rows last
            v_eff = jnp.where(chosen, version_before - flush["staleness"],
                              jnp.int32(_I32_MAX))
            rk = jnp.where(chosen, slot_rank[cidx],
                           jnp.arange(b, dtype=jnp.int32))
            v_s, _, perm = jax.lax.sort(
                (v_eff, rk, jnp.arange(b, dtype=jnp.int32)), num_keys=2)
            cidx_s, chosen_s = cidx[perm], chosen[perm]
            succ_s = flush["succeeded"][perm]
            stale_s, aggw_s = flush["staleness"][perm], \
                flush["agg_weight"][perm]
            if recharge_pct_per_hour > 0.0:
                kplug = jax.random.fold_in(krech, 7)
                plugged = jax.random.bernoulli(kplug, plugged_frac, (n,))
                gain = (recharge_pct_per_hour * flush["round_duration"]
                        / 3600.0)
                battery = jnp.clip(pop.battery_pct + plugged * gain,
                                   0.0, 100.0)
                rejoin = pop.dropped & (battery >= rejoin_pct)
                pop = pop.replace(battery_pct=battery,
                                  dropped=pop.dropped & ~rejoin)
            krech = krecharge
            # stale-start cohort: every flush row trains from the ring
            # slot of the version it downloaded, with its version-anchored
            # success-rank key; masked rows ride along zero-weighted
            slot_i = _ring_lookup(ring, v_s)
            start_params = jax.tree.map(lambda r: r[slot_i], ring.params)
            within = _within_version_rank(v_s, succ_s)
            key_ix = jnp.clip(ring.succ[slot_i] + within, 0,
                              max_concurrency - 1)
            keys = _flush_train_keys(ring.tkey[slot_i], key_ix,
                                     max_concurrency)
            deltas, per_sample, mean_losses = cohort(
                start_params, data_x[cidx_s], data_y[cidx_s], keys)
            finite = finite_rows(deltas)
            good = succ_s & finite
            w = jnp.where(good,
                          pop.n_samples[cidx_s].astype(jnp.float32) * aggw_s,
                          0.0)
            agg = weighted_delta(zero_nonfinite_rows(deltas, finite), w)
            new_params, new_opt = server_update(params, agg, opt, opt_state)
            ok = good.any() & tree_finite(agg)
            params = jax.tree.map(
                lambda a, c: jnp.where(ok, a, c), new_params, params)
            opt_state = jax.tree.map(
                lambda a, c: jnp.where(ok, a, c), new_opt, opt_state)
            su = stat_utility(per_sample, w)
            pop = scatter_stat_util(pop, cidx_s, good, su)
            # ring turnover: flushed holders release, the refill batch
            # retains the (possibly just bumped) live version
            ring = _ring_release(ring, v_s, chosen_s, succ_s)
            ring = _ring_retain(ring, astate.server_version, params,
                                jnp.sum(rchosen).astype(jnp.int32), ktrain)
            slot_rank = slot_rank.at[jnp.where(rchosen, ridx, n)].set(
                jnp.arange(ridx.shape[0], dtype=jnp.int32), mode="drop")
            last_acc = jax.lax.cond(do_eval, eval_acc,
                                    lambda _: last_acc, params)
            out = {
                "completed": cidx_s,
                "comp_chosen": chosen_s,
                "succeeded": succ_s,
                "staleness": jnp.where(chosen_s, stale_s, 0),
                "agg_weight": aggw_s,
                "start_version": jnp.where(chosen_s, v_s, 0),
                "selected": ridx,
                "chosen": rchosen,
                "round_duration": flush["round_duration"],
                "new_dropouts": flush["new_dropouts"],
                "server_clock": astate.server_clock,
                "server_version": astate.server_version,
                "n_inflight": jnp.sum(astate.in_flight).astype(jnp.int32),
                "mean_battery": jnp.mean(pop.battery_pct),
                "fairness": jains_index(pop.times_selected),
                "slot_losses": jnp.where(succ_s, mean_losses, 0.0),
                "test_acc": last_acc,
                "quarantined": jnp.sum(succ_s & ~finite).astype(jnp.int32),
                "update_skipped": (~ok).astype(jnp.int32),
                "energy_spent_j": astate.spent_j,
                "budget_exhausted": astate.exhausted_round,
            }
            return (params, opt_state, pop, st, astate, ring, slot_rank,
                    krech, kloop, last_acc), out

        return jax.lax.scan(scan_step, carry, do_eval)

    return fill, run, evaluate


def _async_runner_statics(cfg: FLConfig, sim_steps: int, energy_model,
                          model_bytes: float, up_bytes):
    """The hashable static tail shared by the scanned and sharded async
    runners (mirrors ``_fused_statics`` plus the FedBuff geometry)."""
    b, c, r = _async_geometry(cfg)
    return (cfg.selector, energy_model,
            None if cfg.deadline_s is None else float(cfg.deadline_s),
            int(sim_steps), int(cfg.local_steps), int(cfg.batch_size),
            float(cfg.client_lr), float(cfg.fedprox_mu), cfg.compression,
            float(cfg.compression_sparsity), cfg.server_opt,
            float(cfg.server_lr), float(cfg.recharge_pct_per_hour),
            float(cfg.plugged_frac), float(cfg.rejoin_pct), b, c,
            float(cfg.staleness_power), r,
            None if cfg.energy_budget_j is None
            else float(cfg.energy_budget_j),
            float(model_bytes),
            None if up_bytes is None else float(up_bytes))


def run_fl_async_scanned(cfg: FLConfig, verbose: bool = False,
                         _capture: Optional[dict] = None) -> FLHistory:
    """:func:`run_fl_async`, fully device-resident: all ``cfg.rounds``
    FedBuff aggregations run inside one jitted ``lax.scan`` (flush →
    stale-start cohort SGD from the in-carry snapshot ring → damped
    aggregation → server update → refill → eval), with zero per-event
    host transfers. Trajectory parity with the host loop is the contract
    — see the module docstring and ``tests/test_async_training_engines``.

    Elastic knobs (``cfg.checkpoint_path`` / ``cfg.checkpoint_every`` /
    ``cfg.resume_from``) split the scan into checkpoint-aligned segments;
    the ring is an ordinary carry rider, so restart parity is bitwise.

    ``_capture`` (tests only): a dict that receives the raw concatenated
    trajectory under ``"traj"``.
    """
    _check_async_cfg(cfg)
    # one-time host->device materialization
    with setup_transfers(), spans.span("fl.setup"):
        (kloop, data, test, params, opt_state, pop, sim_steps, up_bytes,
         energy_model, model_bytes) = _fused_setup(cfg)
        with spans.span("fl.setup.runner"):
            fill, run, evaluate = _async_fused_runner(
                cfg.model, *_async_runner_statics(cfg, sim_steps,
                                                  energy_model, model_bytes,
                                                  up_bytes),
                _auto_pallas(cfg.n_clients, None),
                jax.default_backend() != "tpu")
            st = SelectorState.create(cfg.selector).canonical()
        acc0, init_acc = _untrained_acc(evaluate, params, test)
        carry0, _idx0, _chosen0 = fill(kloop, params, opt_state, pop, st,
                                       acc0)
    hist = _run_fused_elastic(
        cfg, run, carry0, init_acc,
        (data["x"], data["y"], test["x"], test["y"]),
        {"pop_template": pop,
         "restore": lambda state: tuple(state[k] for k in _ASYNC_CARRY)},
        lambda carry: dict(zip(_ASYNC_CARRY, carry)),
        meta=_async_train_meta(cfg, "train-async"),
        history_fn=_async_history, carry_names=_ASYNC_CARRY,
        capture=_capture)
    if verbose:
        _print_fused_history(cfg, hist)
    return hist


# ---------------------------------------------------- sharded training twin
# run_fl_async_scanned over the 1-D `clients` mesh. Per event, inside one
# shard_map body: the flush/refill event step runs shard-local
# (simulation._shard_async_step, index-for-index identical to the
# single-device step), the flush's training data is reassembled with
# one-owner-per-slot psum gathers, and the flush axis is then split EVENLY
# across shards — each shard runs stale-start local SGD for B/S rows from
# the replicated snapshot ring and contributes its partial weighted delta
# via a psum. The server update, ring turnover and eval run on replicated
# state in the outer scan body.
#
# Parity contract vs run_fl_async_scanned: flush/refill/version
# trajectories are index-for-index identical (same rank-bit streams, same
# event arithmetic); the aggregated delta differs in the last ulp (psum of
# per-shard partial tensordots), so params — and everything downstream —
# match within float tolerance rather than bitwise. Mirrors the sync
# sharded contract (`launch/sharded_check.py --train`).


@functools.lru_cache(maxsize=4)
def _sharded_async_fused_runner(model_cfg, sel_cfg, energy_model,
                                deadline_s: Optional[float], sim_steps: int,
                                local_steps: int, batch_size: int,
                                client_lr: float, fedprox_mu: float,
                                compression: str, sparsity: float,
                                server_opt: str, server_lr: float,
                                recharge_pct_per_hour: float,
                                plugged_frac: float, rejoin_pct: float,
                                buffer_size: int, max_concurrency: int,
                                staleness_power: float, ring_size: int,
                                energy_budget_j: Optional[float],
                                model_bytes: float,
                                up_bytes: Optional[float],
                                use_pallas: bool, interpret: bool,
                                mesh, n_real: int, axis_name: str):
    """Cached jitted sharded async-training runners (statics mirror
    :func:`_async_fused_runner` plus the mesh geometry). Returns the same
    segment-callable ``(fill, run, evaluate)`` triple."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    opt = make_server_optimizer(server_opt, server_lr)
    cohort = _cohort_train_fn(model_cfg, local_steps, batch_size, client_lr,
                              fedprox_mu, compression, sparsity,
                              params_axis=0)
    _, _, fill_cfg, refill_cfg = _async_knobs(sel_cfg, buffer_size,
                                              max_concurrency)
    n_shards = mesh.shape[axis_name]
    n_padded = n_real + (-n_real) % n_shards
    n_pad = n_padded - n_real
    b_width = buffer_size
    pad_b = (-b_width) % n_shards
    b_pad = b_width + pad_b
    b_per = b_pad // n_shards
    spec, rep = P(axis_name), P()
    astate_spec = AsyncEventState(t_done=spec, start_version=spec,
                                  server_clock=P(), server_version=P(),
                                  spent_j=P(), exhausted_round=P())

    def _pad_flush(a, fill=0):
        if pad_b == 0:
            return a
        return jnp.concatenate(
            [a, jnp.full((pad_b,) + a.shape[1:], fill, a.dtype)])

    def fill_body(key, st, astate, pop, t_total, cost, bits, slot_rank):
        n_loc = cost.shape[0]
        base = (jax.lax.axis_index(axis_name) * n_loc).astype(jnp.int32)
        st, astate, idx, chosen = _shard_async_fill(
            key, st, astate, pop, t_total, cost, bits, fill_cfg=fill_cfg,
            axis_name=axis_name, n_real=n_real, use_pallas=use_pallas,
            interpret=interpret, energy_budget_j=energy_budget_j)
        own = chosen & (idx >= base) & (idx < base + n_loc)
        slot_rank = slot_rank.at[jnp.where(own, idx - base, n_loc)].set(
            jnp.arange(idx.shape[0], dtype=jnp.int32), mode="drop")
        return st, astate, idx, chosen, slot_rank

    def train_body(ksel, st, astate, pop, t_total, cost, bits, u_rech,
                   slot_rank, x_loc, y_loc, params, ring_params,
                   ring_version, ring_tkey, ring_succ):
        n_loc = cost.shape[0]
        shard_i = jax.lax.axis_index(axis_name)
        base = (shard_i * n_loc).astype(jnp.int32)
        version_before = astate.server_version
        pop, st, astate, flush, (ridx, rchosen), stats = _shard_async_step(
            ksel, st, astate, pop, t_total, cost, bits, jnp.bool_(True),
            refill_cfg=refill_cfg, buffer_size=buffer_size,
            staleness_power=staleness_power, energy_model=energy_model,
            deadline_s=deadline_s, axis_name=axis_name, n_real=n_real,
            n_pad=n_pad, use_pallas=use_pallas, interpret=interpret,
            energy_budget_j=energy_budget_j)
        cidx, chosen = flush["completed"], flush["comp_chosen"]
        # selection-slot ranks BEFORE the refill scatter overwrites them
        rk_g = _slot_gather_i32(slot_rank, cidx, chosen, base, axis_name)
        v_eff = jnp.where(chosen, version_before - flush["staleness"],
                          jnp.int32(_I32_MAX))
        rk = jnp.where(chosen, rk_g, jnp.arange(b_width, dtype=jnp.int32))
        v_s, _, perm = jax.lax.sort(
            (v_eff, rk, jnp.arange(b_width, dtype=jnp.int32)), num_keys=2)
        cidx_s, chosen_s = cidx[perm], chosen[perm]
        succ_s = flush["succeeded"][perm]
        stale_s, aggw_s = flush["staleness"][perm], flush["agg_weight"][perm]
        own_r = rchosen & (ridx >= base) & (ridx < base + n_loc)
        slot_rank = slot_rank.at[jnp.where(own_r, ridx - base, n_loc)].set(
            jnp.arange(ridx.shape[0], dtype=jnp.int32), mode="drop")
        if recharge_pct_per_hour > 0.0:
            # pre-generated sharded uniform stream (prefix-stable: the
            # first n_real draws equal the single-device bernoulli's);
            # pad clients are masked out so they can never recharge-rejoin
            real = (base + jnp.arange(n_loc)) < n_real
            plugged = (u_rech < plugged_frac) & real
            gain = (recharge_pct_per_hour * flush["round_duration"]
                    / 3600.0)
            battery = jnp.clip(pop.battery_pct + plugged * gain, 0.0, 100.0)
            rejoin = pop.dropped & (battery >= rejoin_pct)
            pop = pop.replace(battery_pct=battery,
                              dropped=pop.dropped & ~rejoin)
        # replicated ring lookup + version-anchored train keys
        slot_i = jnp.argmax(ring_version[None, :] == v_s[:, None],
                            axis=1).astype(jnp.int32)
        within = _within_version_rank(v_s, succ_s)
        key_ix = jnp.clip(ring_succ[slot_i] + within, 0,
                          max_concurrency - 1)
        keys = _flush_train_keys(ring_tkey[slot_i], key_ix, max_concurrency)
        start_params = jax.tree.map(lambda r: r[slot_i], ring_params)
        # --- cohort gather: one shard owns each flush row's client -------
        own_c = chosen_s & (cidx_s >= base) & (cidx_s < base + n_loc)
        loc_c = jnp.clip(cidx_s - base, 0, n_loc - 1)

        def gather_data(a_loc):
            shape = (own_c.shape[0],) + (1,) * (a_loc.ndim - 1)
            vals = jnp.where(own_c.reshape(shape), a_loc[loc_c],
                             jnp.zeros((), a_loc.dtype))
            return jax.lax.psum(vals, axis_name)

        xg = _pad_flush(gather_data(x_loc))
        yg = _pad_flush(gather_data(y_loc))
        wg = _slot_gather(pop.n_samples, cidx_s, chosen_s, base, axis_name)
        # --- even flush split: shard i trains rows [i*b_per, (i+1)*b_per)
        sl = shard_i * b_per
        x_sl = jax.lax.dynamic_slice_in_dim(xg, sl, b_per)
        y_sl = jax.lax.dynamic_slice_in_dim(yg, sl, b_per)
        k_sl = jax.lax.dynamic_slice_in_dim(_pad_flush(keys), sl, b_per)
        start_sl = jax.tree.map(
            lambda s: jax.lax.dynamic_slice_in_dim(_pad_flush(s), sl, b_per),
            start_params)
        deltas, per_sample, mean_losses = cohort(start_sl, x_sl, y_sl, k_sl)
        fin_sl = finite_rows(deltas)
        deltas = zero_nonfinite_rows(deltas, fin_sl)
        fin = jax.lax.all_gather(fin_sl, axis_name).reshape(-1)[:b_width]
        good = succ_s & fin
        w_full = jnp.where(good, wg * aggw_s, 0.0)
        wq_p = _pad_flush(w_full)
        w_sl = jax.lax.dynamic_slice_in_dim(wq_p, sl, b_per)
        wn = wq_p / jnp.maximum(jnp.sum(w_full), 1e-9)
        wn_sl = jax.lax.dynamic_slice_in_dim(wn, sl, b_per)
        agg = jax.tree.map(
            lambda d: jax.lax.psum(
                weighted_sum(wn_sl, d), axis_name),
            deltas)
        su = jax.lax.all_gather(
            stat_utility(per_sample, w_sl), axis_name).reshape(-1)
        losses = jax.lax.all_gather(mean_losses, axis_name).reshape(-1)
        pop = scatter_stat_util(pop, loc_c, good & own_c, su[:b_width])
        ts = pop.times_selected.astype(jnp.float32)
        s1 = jax.lax.psum(jnp.sum(ts), axis_name)
        s2 = jax.lax.psum(jnp.sum(jnp.square(ts)), axis_name)
        out = {
            "completed": cidx_s,
            "comp_chosen": chosen_s,
            "succeeded": succ_s,
            "staleness": jnp.where(chosen_s, stale_s, 0),
            "agg_weight": aggw_s,
            "start_version": jnp.where(chosen_s, v_s, 0),
            "selected": ridx,
            "chosen": rchosen,
            "round_duration": flush["round_duration"],
            "new_dropouts": flush["new_dropouts"],
            "server_clock": astate.server_clock,
            "server_version": astate.server_version,
            "n_inflight": stats["n_inflight"],
            "mean_battery": _asum(pop.battery_pct, axis_name) / n_real,
            "fairness": jnp.where(s2 > 0,
                                  jnp.square(s1) / (n_real * s2), 1.0),
            "slot_losses": jnp.where(succ_s, losses[:b_width], 0.0),
            "quarantined": jnp.sum(succ_s & ~fin).astype(jnp.int32),
            "energy_spent_j": astate.spent_j,
            "budget_exhausted": astate.exhausted_round,
            # outer-scan plumbing (popped before the trajectory is emitted)
            "any_good": good.any(),
            "v_eff": v_s,
        }
        return pop, st, astate, slot_rank, agg, out

    fill_smapped = jax.shard_map(
        fill_body, mesh=mesh,
        in_specs=(rep, rep, astate_spec, spec, spec, spec, spec, spec),
        out_specs=(rep, astate_spec, rep, rep, spec), check_vma=False)
    smapped = jax.shard_map(
        train_body, mesh=mesh,
        in_specs=(rep, rep, astate_spec, spec, spec, spec, spec, spec,
                  spec, spec, spec, rep, rep, rep, rep, rep),
        out_specs=(spec, rep, astate_spec, spec, rep, rep), check_vma=False)
    shard = NamedSharding(mesh, spec)

    @jax.jit
    def evaluate(params, test_x, test_y):
        return _test_accuracy(model_cfg, params, test_x, test_y)

    @jax.jit
    def fill(kloop, params, opt_state, pop, st, last_acc, t_total, cost):
        kloop, ksel, ktrain, krecharge = jax.random.split(kloop, 4)
        astate = AsyncEventState.create(n_padded)
        slot_rank = jnp.zeros((n_padded,), jnp.int32)
        bits = jax.lax.with_sharding_constraint(
            _rank_bits(ksel, n_padded), shard)
        st, astate, idx0, chosen0, slot_rank = fill_smapped(
            ksel, st, astate, pop, t_total, cost, bits, slot_rank)
        ring = _ring_create(params, ring_size)
        ring = _ring_retain(ring, jnp.int32(0), params,
                            jnp.sum(chosen0).astype(jnp.int32), ktrain)
        carry = (params, opt_state, pop, st, astate, ring, slot_rank,
                 krecharge, kloop, last_acc)
        return carry, idx0, chosen0

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(do_eval, carry, data_x, data_y, test_x, test_y, t_total, cost):
        def eval_acc(p):
            return _test_accuracy(model_cfg, p, test_x, test_y)

        def scan_step(carry, do_eval):
            (params, opt_state, pop, st, astate, ring, slot_rank, krech,
             kloop, last_acc) = carry
            kloop, ksel, ktrain, krecharge = jax.random.split(kloop, 4)
            bits = jax.lax.with_sharding_constraint(
                _rank_bits(ksel, n_padded), shard)
            kplug = jax.random.fold_in(krech, 7)
            u_rech = jax.lax.with_sharding_constraint(
                jax.random.uniform(kplug, (n_padded,)), shard)
            pop, st, astate, slot_rank, agg, out = smapped(
                ksel, st, astate, pop, t_total, cost, bits, u_rech,
                slot_rank, data_x, data_y, params, ring.params,
                ring.version, ring.tkey, ring.succ)
            new_params, new_opt = server_update(params, agg, opt, opt_state)
            ok = out.pop("any_good") & tree_finite(agg)
            params = jax.tree.map(
                lambda a, c: jnp.where(ok, a, c), new_params, params)
            opt_state = jax.tree.map(
                lambda a, c: jnp.where(ok, a, c), new_opt, opt_state)
            v_s = out.pop("v_eff")
            ring = _ring_release(ring, v_s, out["comp_chosen"],
                                 out["succeeded"])
            ring = _ring_retain(ring, astate.server_version, params,
                                jnp.sum(out["chosen"]).astype(jnp.int32),
                                ktrain)
            krech = krecharge
            last_acc = jax.lax.cond(do_eval, eval_acc,
                                    lambda _: last_acc, params)
            out = dict(out, test_acc=last_acc,
                       update_skipped=(~ok).astype(jnp.int32))
            return (params, opt_state, pop, st, astate, ring, slot_rank,
                    krech, kloop, last_acc), out

        return jax.lax.scan(scan_step, carry, do_eval)

    return fill, run, evaluate


def run_fl_async_sharded(cfg: FLConfig, verbose: bool = False, mesh=None,
                         n_shards: Optional[int] = None,
                         _capture: Optional[dict] = None) -> FLHistory:
    """:func:`run_fl_async_scanned` on the `clients` mesh: population,
    data and event state shard-resident, the snapshot ring replicated,
    flush-cohort local SGD data-parallel across shards, weighted deltas
    psum-merged. Defaults to a mesh over all visible devices.

    Checkpoints store the population/event-state/slot-rank leaves TRIMMED
    to the real clients (the pad tail is provably inert), which makes
    "train-async" snapshots portable across device counts AND across the
    scanned/sharded engines."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_client_mesh
    from repro.launch.sharding import population_sharding

    _check_async_cfg(cfg)
    _, _, ring_size = _async_geometry(cfg)
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
            fill, run, evaluate = _sharded_async_fused_runner(
                cfg.model, *_async_runner_statics(cfg, sim_steps,
                                                  energy_model, model_bytes,
                                                  up_bytes),
                _auto_pallas(n_real, None), jax.default_backend() != "tpu",
                mesh, n_real, axis_name)
            st = SelectorState.create(cfg.selector).canonical()
        acc0, init_acc = _untrained_acc(evaluate, params, test)
        carry0, _idx0, _chosen0 = fill(kloop, params, opt_state, pop, st,
                                       acc0, t_total, cost)
    n_padded = pop.n
    rep_sh = NamedSharding(mesh, P())
    astate_sharding = AsyncEventState(
        t_done=sharding, start_version=sharding, server_clock=rep_sh,
        server_version=rep_sh, spent_j=rep_sh, exhausted_round=rep_sh)

    def _restore(state):
        rpop = jax.device_put(
            pad_population(state["pop"], mesh.shape[axis_name]), sharding)
        rastate = jax.device_put(_pad_astate(state["astate"], n_padded),
                                 astate_sharding)
        rsr = jax.device_put(
            jnp.concatenate([state["slot_rank"],
                             jnp.zeros((n_padded - n_real,), jnp.int32)]),
            sharding)
        return (state["params"], state["opt_state"], rpop, state["st"],
                rastate, state["ring"], rsr, state["krech"],
                state["kloop"], state["last_acc"])

    def _save_state(carry):
        s = dict(zip(_ASYNC_CARRY, carry))
        s["pop"] = jax.tree.map(lambda x: x[:n_real], s["pop"])
        s["astate"] = s["astate"]._replace(
            t_done=s["astate"].t_done[:n_real],
            start_version=s["astate"].start_version[:n_real])
        s["slot_rank"] = s["slot_rank"][:n_real]
        return s

    hist = _run_fused_elastic(
        cfg, run, carry0, init_acc,
        (data_x, data_y, test["x"], test["y"], t_total, cost),
        {"pop_template": pop0, "restore": _restore,
         "overrides": {"astate": AsyncEventState.create(n_real),
                       "slot_rank": jnp.zeros((n_real,), jnp.int32)}},
        _save_state,
        meta=_async_train_meta(cfg, "train-async"),
        history_fn=_async_history, carry_names=_ASYNC_CARRY,
        capture=_capture)
    if verbose:
        _print_fused_history(cfg, hist)
    return hist
