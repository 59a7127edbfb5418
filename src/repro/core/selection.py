"""Client selectors: EAFL (the paper), Oort, and Random.

EAFL and Oort share the exploration/exploitation skeleton (Oort OSDI'21,
which EAFL modifies *only* in the reward definition, Eq. 1):

  - an epsilon fraction of the K slots explores unexplored clients,
    epsilon decaying per round;
  - the rest exploits: top-reward explored clients, with a UCB-style
    staleness bonus so long-unselected clients get re-examined;
  - a pacer maintains the developer-preferred round duration T used by the
    system-efficiency penalty in Eq. 2.

The hot path is device-resident: ``select_device`` is a single jitted
function (exploration via the Gumbel-top-k trick, exploitation on the
score; both top-ks via ``jax.lax.top_k`` or, above ``PALLAS_N_THRESHOLD``
on TPU, the pruned Pallas block top-k of ``kernels.topk_select``, fused
with the reward for exploitation), returning fixed-shape ``(k,)`` indices plus
a chosen-slot mask so it composes with ``jax.lax.scan``. ``select`` is the
thin host wrapper that trims to the chosen slots; ``select_host`` keeps the
original eager numpy implementation as the parity reference.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rewards
from repro.core.clients import ClientPopulation, pad_population
from repro.kernels import topk_select as _tk

# Counter-based (partitionable) threefry: ``random.bits(key, (n,))`` becomes
# an elementwise hash of the position, so (a) XLA shards rank-bit generation
# with the population instead of replicating the full stream on every device
# of a `clients` mesh, and (b) the stream is prefix-stable — the first N
# elements are identical for any padded length, which is what makes the
# padded sharded engine bit-compatible with the unpadded single-device path.
# Set once at import (NOT per engine entry point: parity between the host /
# single-device / sharded paths requires every path to draw the same
# stream, and flipping the flag mid-process would split them). An explicit
# user setting via the standard env var wins.
import os as _os

if "JAX_THREEFRY_PARTITIONABLE" not in _os.environ:
    jax.config.update("jax_threefry_partitionable", True)

# population size above which the Pallas kernel is preferred on TPU;
# below it a single lax.top_k is faster than a two-level tournament.
PALLAS_N_THRESHOLD = 131_072


def _top_idx(x, k: int, use_pallas: bool, interpret: bool) -> jnp.ndarray:
    """``lax.top_k(x, k)``'s indices, through the pruned Pallas kernel
    where ``use_pallas``."""
    if use_pallas:
        return _tk.topk_scores(x, k, interpret=interpret)[1]
    return jax.lax.top_k(x, k)[1]


@dataclass(frozen=True)
class SelectorConfig:
    kind: str                     # eafl | oort | random | eafl-epj
    k: int = 10
    f: float = 0.25               # Eq. 1 mixing weight (paper uses 0.25)
    alpha: float = 2.0            # Eq. 2 straggler penalty exponent
    epsilon0: float = 0.9
    epsilon_decay: float = 0.98
    epsilon_min: float = 0.2
    ucb_c: float = 0.1
    pacer_t0: float = 120.0       # initial preferred round duration (s)
    pacer_delta: float = 30.0
    pacer_max: float = 1800.0
    normalize_reward: bool = True


@dataclass
class SelectorState:
    """Selector carry. All fields are scalars (python or jnp 0-d) so the
    state is a 4-leaf pytree that flows through jit and lax.scan."""

    round: int = 0
    epsilon: float = 0.9
    pacer_T: float = 120.0
    util_ema: float = 0.0

    @classmethod
    def create(cls, cfg: SelectorConfig) -> "SelectorState":
        return cls(round=0, epsilon=cfg.epsilon0, pacer_T=cfg.pacer_t0)

    def canonical(self) -> "SelectorState":
        """Strong-typed device scalars (required as a lax.scan carry)."""
        return SelectorState(
            round=jnp.asarray(self.round, jnp.int32),
            epsilon=jnp.asarray(self.epsilon, jnp.float32),
            pacer_T=jnp.asarray(self.pacer_T, jnp.float32),
            util_ema=jnp.asarray(self.util_ema, jnp.float32))


jax.tree_util.register_pytree_node(
    SelectorState,
    lambda s: ((s.round, s.epsilon, s.pacer_T, s.util_ema), None),
    lambda _, leaves: SelectorState(*leaves))


def _rank_bits(key, n: int) -> jnp.ndarray:
    """Random ranking keys equivalent to Gumbel top-k from the same key.

    ``uniform(key)`` keeps the top 23 bits of ``bits(key)`` as the f32
    mantissa, and ``gumbel = -log(-log(uniform))`` is strictly increasing,
    so ranking ``bits >> 9`` yields index-for-index (and tie-for-tie) the
    same top-k as ranking the Gumbels — this is what makes the device path
    bit-compatible with ``jax.random.choice(replace=False)`` and the host
    reference while skipping the float transforms. The 23-bit keys are
    returned as exact f32 integers: XLA's CPU TopK fast path is
    float-only (integer top_k falls back to a full sort).
    """
    return (jax.random.bits(key, (n,), jnp.uint32) >> 9).astype(jnp.float32)


def ucb_bonus(staleness, t, c):
    """The exploration bonus ``c * sqrt(log(t + 1) / max(staleness, 1))``.

    Shared machinery: the client selector uses it with ``staleness`` =
    rounds since the client was last picked (:func:`_ucb_bonus`), and the
    knob controller (:mod:`repro.federated.controller`) with ``staleness``
    = pull count of the arm — one formula, so the two explorers cannot
    drift."""
    t_f = jnp.asarray(t, jnp.float32)
    return c * jnp.sqrt(jnp.log(t_f + 1.0) / jnp.maximum(staleness, 1))


def _ucb_bonus(cfg, pop: ClientPopulation, rnd) -> jnp.ndarray:
    return ucb_bonus(rnd - pop.last_round, rnd, cfg.ucb_c)


def _score_inputs(cfg: SelectorConfig, state: SelectorState,
                  pop: ClientPopulation, predicted_cost_pct):
    """Elementwise pieces of the exploitation score.

    Returns ``(a, b, valid, mask, ucb, mode)`` of *raw* (un-normalised)
    score inputs: ``valid`` is the normalisation population (Eq. 1's
    candidate set), ``mask`` the selectable set, and the final score is
    ``where(mask, mix(a, b) * (1 + ucb), -inf)`` with ``mix`` given by
    ``mode`` (see :func:`_mix_scores` and the Pallas ``topk_reward``
    kernel, its fused twin).
    """
    util = rewards.oort_utility(pop.stat_util, pop.last_duration,
                                state.pacer_T, cfg.alpha)
    valid = pop.alive
    ucb = _ucb_bonus(cfg, pop, state.round)
    if cfg.kind == "oort":
        return util, jnp.zeros_like(util), valid, valid, ucb, "oort"
    if cfg.kind == "eafl":
        power = rewards.projected_power(pop.battery_pct, predicted_cost_pct)
        return util, power, valid, valid, ucb, "eafl"
    if cfg.kind == "eafl-epj":
        # beyond-paper variant: utility per unit energy, gated on surviving
        # the round — ranks by how much statistical progress each %-battery
        # buys instead of mixing the scales linearly.
        survives = pop.battery_pct > predicted_cost_pct
        return util, predicted_cost_pct, valid, valid & survives, ucb, \
            "eafl-epj"
    raise ValueError(cfg.kind)


def _mix_scores(cfg: SelectorConfig, a, b, valid, mask, ucb,
                mode: str, norm_stats=None) -> jnp.ndarray:
    f = cfg.f
    if mode == "oort":
        s = a
    elif mode == "eafl":
        if cfg.normalize_reward:
            # min-max normalisation of util and power over the candidate
            # set, folded into scalar affine coefficients so no normalised
            # million-entry array is ever materialised:
            #   f*(a-lo_a)/ra + (1-f)*(b-lo_b)/rb = ca*a + cb*b + c0
            # ``norm_stats`` lets the sharded path inject globally-reduced
            # (lo, range) pairs; the arithmetic below is shared, so shard
            # scores stay bitwise identical to the single-device scores.
            if norm_stats is None:
                lo_a, ra = rewards.minmax_range(a, valid)
                lo_b, rb = rewards.minmax_range(b, valid)
            else:
                (lo_a, ra), (lo_b, rb) = norm_stats
            ca, cb = f / ra, (1.0 - f) / rb
            c0 = -(ca * lo_a + cb * lo_b)
            s = ca * a + cb * b + c0
        else:
            s = f * a + (1.0 - f) * b
    elif mode == "eafl-epj":
        s = a / jnp.maximum(b, 1e-3)
    else:
        raise ValueError(mode)
    return jnp.where(mask, s * (1.0 + ucb), -jnp.inf)


def compute_scores(cfg: SelectorConfig, state: SelectorState,
                   pop: ClientPopulation,
                   predicted_cost_pct: jnp.ndarray) -> jnp.ndarray:
    """Per-client selection score for the exploitation slots."""
    a, b, valid, mask, ucb, mode = _score_inputs(cfg, state, pop,
                                                 predicted_cost_pct)
    return _mix_scores(cfg, a, b, valid, mask, ucb, mode)


@jax.named_scope("select")
def _device_select(key, cfg: SelectorConfig, state: SelectorState,
                   pop: ClientPopulation, predicted_cost_pct,
                   use_pallas: bool, interpret: bool):
    """Fully traced selection step with fixed output shapes.

    Returns ``(idx (k,), chosen (k,) bool, new_state)`` where only the
    slots with ``chosen`` are real picks (exploit slots first, then
    exploration), mirroring the host reference ordering exactly.
    """
    n = pop.n
    k = min(cfg.k, n)
    state = SelectorState(state.round + 1, state.epsilon, state.pacer_T,
                          state.util_ema)
    valid = pop.alive
    k_eff = jnp.minimum(k, jnp.sum(valid)).astype(jnp.int32)
    slots = jnp.arange(k)

    if cfg.kind == "random":
        g = jnp.where(valid, _rank_bits(key, n), -1.0)
        idx = _top_idx(g, k, use_pallas, interpret)
        return idx.astype(jnp.int32), slots < k_eff, state

    explored = pop.explored & valid
    unexplored = valid & ~explored

    a, b, norm_valid, mask, ucb, mode = _score_inputs(cfg, state, pop,
                                                      predicted_cost_pct)
    mask = mask & explored

    n_unexp = jnp.sum(unexplored).astype(jnp.int32)
    # exploit slots are capped by the *selectable* explored pool (for
    # eafl-epj the mask also excludes clients that would die mid-round),
    # so slots never overflow onto -inf-scored clients
    n_expl_avail = jnp.sum(mask).astype(jnp.int32)
    n_explore = jnp.minimum(
        jnp.round(state.epsilon * k_eff).astype(jnp.int32), n_unexp)
    n_exploit = jnp.minimum(k_eff - n_explore, n_expl_avail)
    n_explore = jnp.minimum(k_eff - n_exploit, n_unexp)
    if use_pallas:
        if mode == "eafl" and cfg.normalize_reward:
            a = rewards.minmax_normalize(a, norm_valid)
            b = rewards.minmax_normalize(b, norm_valid)
        _, exploit_idx = _tk.topk_reward(a, b, mask, ucb=ucb, f=cfg.f, k=k,
                                         mode=mode, interpret=interpret)
    else:
        score = _mix_scores(cfg, a, b, norm_valid, mask, ucb, mode)
        _, exploit_idx = jax.lax.top_k(score, k)

    g = jnp.where(unexplored, _rank_bits(key, n), -1.0)
    explore_idx = _top_idx(g, k, use_pallas, interpret)

    take_exploit = slots < n_exploit
    idx = jnp.where(take_exploit, exploit_idx,
                    explore_idx[jnp.clip(slots - n_exploit, 0, k - 1)])
    chosen = slots < (n_exploit + n_explore)

    # epsilon decay + pacer update on the *exploited* utility mass; the host
    # reference skips all of this when no client is selectable, so gate on
    # k_eff to keep the state trajectories identical.
    any_pick = k_eff > 0
    n_chosen = jnp.sum(chosen)
    sel_util = jnp.sum(jnp.where(chosen, pop.stat_util[idx], 0.0)) \
        / jnp.maximum(n_chosen, 1)
    epsilon = jnp.where(
        any_pick,
        jnp.maximum(cfg.epsilon_min, state.epsilon * cfg.epsilon_decay),
        state.epsilon)
    slow = (state.util_ema > 0.0) & (sel_util < 0.95 * state.util_ema)
    pacer = jnp.where(
        any_pick & slow,
        jnp.minimum(cfg.pacer_max, state.pacer_T + cfg.pacer_delta),
        state.pacer_T)
    ema = jnp.where(any_pick, 0.9 * state.util_ema + 0.1 * sel_util,
                    state.util_ema)
    return (idx.astype(jnp.int32), chosen,
            SelectorState(state.round, epsilon, pacer, ema))


select_device = partial(jax.jit, static_argnames=(
    "cfg", "use_pallas", "interpret"))(_device_select)


# ------------------------------------------------------------------ sharded
# Two-level selection over a `clients` mesh axis: each shard generates its
# local top-k candidates (the same structure the Pallas kernel uses per
# block), an all-gather merges the S*k candidates, and a tiny global top-k
# finishes. Candidates are gathered in shard order and each shard emits
# ties lowest-local-index first, so the merged flat order is ascending
# global index — exactly ``lax.top_k``'s tie-breaking over the full array.
# Combined with bitwise-identical scores (shared `_mix_scores` arithmetic,
# exactly-associative min/max collectives for the normalisation stats, and
# prefix-stable partitionable rank bits) the sharded output is
# index-for-index identical to :func:`select_device`.

def _merge_candidates(v_loc, i_loc, k: int, axis_name: str):
    """All-gather per-shard candidates (values + GLOBAL indices) and finish
    with one tiny global top-k. Candidates arrive in shard order and each
    shard emits ties lowest-index-first, so among equal values the flat
    gather order is ascending global index — `lax.top_k` tie-breaking."""
    v_all = jax.lax.all_gather(v_loc, axis_name).reshape(-1)
    i_all = jax.lax.all_gather(i_loc, axis_name).reshape(-1)
    _, pos = jax.lax.top_k(v_all, k)
    return i_all[pos]


def _merge_topk(g_loc, k: int, k_loc: int, base, axis_name: str):
    """Per-shard top-k_loc + candidate merge (exact two-level tournament;
    tie-identical to single-device ``lax.top_k(g, k)``)."""
    v_loc, i_loc = jax.lax.top_k(g_loc, k_loc)
    return _merge_candidates(v_loc, i_loc + base, k, axis_name)


def _slot_gather(x_loc, idx, mask, base, axis_name: str, fill=0.0):
    """Gather ``x_loc[idx - base]`` for the (k,) global ``idx`` slots where
    ``mask`` — exactly one shard owns each slot, so a psum reassembles the
    replicated (k,) result without reordering any float arithmetic."""
    n_loc = x_loc.shape[0]
    in_range = mask & (idx >= base) & (idx < base + n_loc)
    loc = jnp.clip(idx - base, 0, n_loc - 1)
    vals = jnp.where(in_range, x_loc[loc].astype(jnp.float32), fill)
    return jax.lax.psum(vals, axis_name)


def _shard_select(key, state: SelectorState, pop: ClientPopulation,
                  predicted_cost_pct, bits,
                  *, cfg: SelectorConfig, axis_name: str, n_real: int,
                  use_pallas: bool, interpret: bool):
    """Shard-local body of the sharded selection step (call under
    ``shard_map`` over ``axis_name``).

    ``pop``/``predicted_cost_pct``/``bits`` are this shard's (n_shard,)
    slices of the padded population (pad clients are dead: ``alive`` False,
    ``explored`` True); ``bits`` is the global rank-bit stream generated
    outside the shard_map (prefix-stable, see module flag above). Returns
    replicated ``(idx (k,), chosen (k,) bool, new_state)`` matching
    :func:`_device_select` on the unpadded population index-for-index.
    """
    n_loc = predicted_cost_pct.shape[0]
    k = min(cfg.k, n_real)
    k_loc = min(k, n_loc)
    base = (jax.lax.axis_index(axis_name) * n_loc).astype(jnp.int32)
    state = SelectorState(state.round + 1, state.epsilon, state.pacer_T,
                          state.util_ema)
    valid = pop.alive
    k_eff = jnp.minimum(k, jax.lax.psum(
        jnp.sum(valid), axis_name)).astype(jnp.int32)
    slots = jnp.arange(k)

    if cfg.kind == "random":
        g = jnp.where(valid, bits, -1.0)
        idx = _merge_topk(g, k, k_loc, base, axis_name)
        return idx.astype(jnp.int32), slots < k_eff, state

    explored = pop.explored & valid
    unexplored = valid & ~explored

    a, b, norm_valid, mask, ucb, mode = _score_inputs(cfg, state, pop,
                                                      predicted_cost_pct)
    mask = mask & explored
    norm_stats = None
    if mode == "eafl" and cfg.normalize_reward:
        norm_stats = (rewards.minmax_range_shard(a, norm_valid, axis_name),
                      rewards.minmax_range_shard(b, norm_valid, axis_name))

    n_unexp = jax.lax.psum(jnp.sum(unexplored), axis_name).astype(jnp.int32)
    n_expl_avail = jax.lax.psum(jnp.sum(mask), axis_name).astype(jnp.int32)
    n_explore = jnp.minimum(
        jnp.round(state.epsilon * k_eff).astype(jnp.int32), n_unexp)
    n_exploit = jnp.minimum(k_eff - n_explore, n_expl_avail)
    n_explore = jnp.minimum(k_eff - n_exploit, n_unexp)

    if use_pallas:
        if mode == "eafl" and cfg.normalize_reward:
            a = rewards.minmax_normalize(a, norm_valid, norm_stats[0])
            b = rewards.minmax_normalize(b, norm_valid, norm_stats[1])
        # per-shard leg of the tournament is the Pallas block merge itself
        v_loc, i_loc = _tk.topk_reward(a, b, mask, ucb=ucb, f=cfg.f,
                                       k=k_loc, mode=mode,
                                       interpret=interpret,
                                       index_offset=base)
        exploit_idx = _merge_candidates(v_loc, i_loc, k, axis_name)
    else:
        score = _mix_scores(cfg, a, b, norm_valid, mask, ucb, mode,
                            norm_stats)
        exploit_idx = _merge_topk(score, k, k_loc, base, axis_name)

    g = jnp.where(unexplored, bits, -1.0)
    explore_idx = _merge_topk(g, k, k_loc, base, axis_name)

    take_exploit = slots < n_exploit
    idx = jnp.where(take_exploit, exploit_idx,
                    explore_idx[jnp.clip(slots - n_exploit, 0, k - 1)])
    chosen = slots < (n_exploit + n_explore)

    # state update: gather stat_util per chosen slot (one owner per slot,
    # psum-reassembled), then reduce in slot order — bitwise identical to
    # the single-device `sum(where(chosen, stat_util[idx], 0))`.
    any_pick = k_eff > 0
    n_chosen = jnp.sum(chosen)
    sel_vals = _slot_gather(pop.stat_util, idx, chosen, base, axis_name)
    sel_util = jnp.sum(jnp.where(chosen, sel_vals, 0.0)) \
        / jnp.maximum(n_chosen, 1)
    epsilon = jnp.where(
        any_pick,
        jnp.maximum(cfg.epsilon_min, state.epsilon * cfg.epsilon_decay),
        state.epsilon)
    slow = (state.util_ema > 0.0) & (sel_util < 0.95 * state.util_ema)
    pacer = jnp.where(
        any_pick & slow,
        jnp.minimum(cfg.pacer_max, state.pacer_T + cfg.pacer_delta),
        state.pacer_T)
    ema = jnp.where(any_pick, 0.9 * state.util_ema + 0.1 * sel_util,
                    state.util_ema)
    return (idx.astype(jnp.int32), chosen,
            SelectorState(state.round, epsilon, pacer, ema))


def make_sharded_select_step(cfg: SelectorConfig, mesh, n_real: int,
                             use_pallas: bool = False,
                             interpret: bool = False,
                             axis_name: str = "clients"):
    """Jitted sharded selection step over a 1-D `clients` mesh.

    Returns ``step(key, state, pop, predicted_cost_pct) -> (idx, chosen,
    new_state)``. Inputs may be unpadded (the step pads in-trace to a
    multiple of the mesh size — pad clients are dead, see
    ``clients.pad_population``) or already padded and sharded over
    ``axis_name``; outputs are replicated and identical to
    :func:`select_device` on the unpadded inputs.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_shards = mesh.shape[axis_name]
    n_padded = n_real + (-n_real) % n_shards
    spec = P(axis_name)
    body = jax.shard_map(
        partial(_shard_select, cfg=cfg, axis_name=axis_name, n_real=n_real,
                use_pallas=use_pallas, interpret=interpret),
        mesh=mesh,
        in_specs=(P(), P(), spec, spec, spec),
        out_specs=(P(), P(), P()),
        check_vma=False)

    @jax.jit
    def step(key, state, pop, predicted_cost_pct):
        if pop.n != n_padded:
            pop = pad_population(pop, n_shards)
            predicted_cost_pct = jnp.pad(predicted_cost_pct,
                                         (0, n_padded - n_real))
        # prefix-stable rank bits, generated sharded (partitionable threefry)
        bits = jax.lax.with_sharding_constraint(
            _rank_bits(key, n_padded), NamedSharding(mesh, spec))
        return body(key, state, pop, predicted_cost_pct, bits)

    return step


def _auto_pallas(n: int, use_pallas: Optional[bool]) -> bool:
    if use_pallas is None:
        return jax.default_backend() == "tpu" and n >= PALLAS_N_THRESHOLD
    return use_pallas


def select(key, cfg: SelectorConfig, state: SelectorState,
           pop: ClientPopulation,
           predicted_cost_pct: Optional[jnp.ndarray] = None,
           use_pallas: Optional[bool] = None,
           interpret: Optional[bool] = None,
           ) -> Tuple[np.ndarray, SelectorState]:
    """Pick K clients. Returns (indices (<=K,), new_state).

    Thin host facade over the jitted :func:`select_device`; the only host
    work is trimming the fixed-shape output to the chosen slots.
    """
    if predicted_cost_pct is None:
        predicted_cost_pct = jnp.zeros((pop.n,), jnp.float32)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    idx, chosen, new_state = select_device(
        key, cfg, state, pop, predicted_cost_pct,
        use_pallas=_auto_pallas(pop.n, use_pallas), interpret=interpret)
    idx = np.asarray(idx)[np.asarray(chosen)]
    return idx.astype(np.int64), new_state


def select_host(key, cfg: SelectorConfig, state: SelectorState,
                pop: ClientPopulation,
                predicted_cost_pct: Optional[jnp.ndarray] = None,
                ) -> Tuple[np.ndarray, SelectorState]:
    """The original eager host implementation (numpy argsort). Kept as the
    parity oracle for :func:`select_device` and as the baseline leg of
    ``benchmarks/selection_scale.py``."""
    valid = np.asarray(pop.alive)
    n_valid = int(valid.sum())
    k = min(cfg.k, n_valid)
    state = SelectorState(state.round + 1, state.epsilon, state.pacer_T,
                          state.util_ema)
    if k == 0:
        return np.zeros((0,), np.int64), state

    if cfg.kind == "random":
        p = valid / valid.sum()
        idx = jax.random.choice(key, pop.n, (k,), replace=False,
                                p=jnp.asarray(p))
        return np.asarray(idx).astype(np.int64), state

    if predicted_cost_pct is None:
        predicted_cost_pct = jnp.zeros((pop.n,), jnp.float32)

    explored = np.asarray(pop.explored) & valid
    unexplored = valid & ~explored
    score = np.array(compute_scores(cfg, state, pop, predicted_cost_pct))
    score[~explored] = -np.inf
    n_explore = min(int(round(float(state.epsilon) * k)),
                    int(unexplored.sum()))
    # exploit slots are capped by the *selectable* explored pool (finite
    # score: for eafl-epj this excludes clients that would die mid-round)
    n_exploit = min(k - n_explore, int((score > -np.inf).sum()))
    n_explore = k - n_exploit  # hand leftovers back to exploration
    n_explore = min(n_explore, int(unexplored.sum()))

    picks = []
    if n_exploit > 0:
        picks.append(np.argsort(-score, kind="stable")[:n_exploit])
    if n_explore > 0:
        g = np.array(jax.random.gumbel(key, (pop.n,)))
        g[~unexplored] = -np.inf
        picks.append(np.argsort(-g, kind="stable")[:n_explore])
    idx = np.concatenate(picks) if picks else np.zeros((0,), np.int64)

    # epsilon decay + pacer update on the *exploited* utility mass
    epsilon = max(cfg.epsilon_min, float(state.epsilon) * cfg.epsilon_decay)
    pacer_T = float(state.pacer_T)
    util_ema = float(state.util_ema)
    sel_util = float(np.asarray(pop.stat_util)[idx].mean()) if len(idx) else 0.0
    if util_ema > 0.0 and sel_util < 0.95 * util_ema:
        pacer_T = min(cfg.pacer_max, pacer_T + cfg.pacer_delta)
    util_ema = 0.9 * util_ema + 0.1 * sel_util
    return idx.astype(np.int64), SelectorState(state.round, epsilon, pacer_T,
                                               util_ema)
