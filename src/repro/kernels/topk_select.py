"""EAFL reward + top-k client selection Pallas kernels (TPU target).

The paper's selection at production scale: for millions of registered
clients, an exact top-k over a score row split into VMEM-sized blocks.
Each block emits its own top-k candidates (values + global indices) by
repeated max+mask picks, and one tiny final ``lax.top_k`` merges the
``n_blocks * k`` candidates: an exact two-level tournament.

Two passes, both ``pallas_call``:

1. ``_score_kernel`` (exploitation only) computes the fused reward once
   per block, writes the reward row and the block's maximum. Three score
   variants (``mode``), all multiplied by the Oort/EAFL UCB staleness
   bonus ``(1 + ucb)`` and masked outside ``valid``:

     eafl      f*a + (1-f)*b          (Eq. 1: a=norm. utility, b=norm. power)
     oort      a                      (a = Oort utility, Eq. 2)
     eafl-epj  a / max(b, 1e-3)       (a = utility, b = predicted %-battery)

   The exploration score (rank bits of the unexplored clients) needs no
   kernel: XLA writes the row and takes the block maxima.

2. ``_pick_kernel`` picks each block's candidates, pruned by a threshold
   ``t0`` read from SMEM (scalar prefetch): ``t0`` is the k-th largest of
   the ``n_blocks`` block maxima. Block ``b`` counts its entries above
   ``t0`` (``gt_b``) and equal to it (``eq_b``), and the blocks run in
   order, carrying the ties seen so far (``eq_before_b``, in SMEM). It
   makes ``min(k, gt_b + min(eq_b, max(0, k - eq_before_b)))`` serial
   picks instead of ``k``.

Why the result stays exact, ties included: k different blocks each hold
an entry ``>= t0``, so the k-th largest score overall is ``>= t0`` and
every member of the exact top-k (value descending, index ascending)
scores ``>= t0``. Members that score exactly ``t0`` are the lowest-index
ties, so at most ``max(0, k - eq_before_b)`` of them lie in block ``b``.
Within a block the members are a prefix of the block's own order (the
entries above ``t0``, then the ties by index), no longer than its pick
count, so the pruned picks emit every one of them, and the k members
outrank every other emitted candidate and the ``-inf`` placeholders of
skipped slots. Equal values are emitted lowest position first and
blocks are merged in index order, so the merge keeps ``lax.top_k``'s
tie-breaking.

Pruning needs ``n_blocks >= k``; below that ``t0`` is ``-inf`` and every
block makes ``min(k, block_n)`` picks, the unpruned loop. Where fewer
than k blocks hold a valid entry, ``t0`` is the masked-entry value: the
blocks pick their valid entries, and only the first blocks pick masked
ones, k in all, as ``lax.top_k`` fills its tail with the lowest-index
masked entries. Each block reports its trip count:
code traced inside ``with pick_tally() as t`` reads the serial picks its
top-k calls made (``t.picks``) against the ``n_blocks * k`` of the
unpruned loop (``t.slots``).

Masked entries score a finite ``SENTINEL`` (not ``-inf``) so that when
``k`` exceeds the valid count the repeated max still walks distinct,
lowest-index-first candidates (``lax.top_k`` tie-breaking) instead of
re-emitting an already picked one. Sentinel picks therefore surface with
value ``SENTINEL`` where the jnp oracle reports ``-inf``; they are never
preferred over any valid candidate. The tail of a row is padded with the
lowest finite f32, below every score and behind every real entry.

TPU layout: a row is viewed as ``(N/128, 128)`` so every block is a
whole number of (8, 128) f32 tiles. The picks use only full-block
reductions and iota compares (max, then the lowest position holding it,
then mask that position): no dynamic vector index or update. Each
block's picks collect into one lane-dense ``(1, k_pad)`` row, written
once. Scores must be finite (masked and padded entries are).
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Iterator, List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 4096
NEG_INF = -jnp.inf
SENTINEL = -3e38          # masked-entry score: below any real reward, > -inf
PAD = float(jnp.finfo(jnp.float32).min)   # tail padding, below SENTINEL
MODES = ("eafl", "oort", "eafl-epj")
LANES = 128
TILE = 8 * LANES          # one f32 (8, 128) tile: the block-size quantum


class PickTally:
    """The serial picks of the top-k calls traced while it is open
    (``picks``, an int32 scalar of that trace) and the picks the unpruned
    loop would make (``slots``, n_blocks * k a call, static)."""

    def __init__(self):
        self.picks = jnp.int32(0)
        self.slots = 0


_local = threading.local()


def _tallies() -> List[PickTally]:
    if not hasattr(_local, "tallies"):
        _local.tallies = []
    return _local.tallies


@contextlib.contextmanager
def pick_tally() -> Iterator[PickTally]:
    """Count the top-k calls traced inside the block, in the innermost
    open tally; read it inside the same trace."""
    tally = PickTally()
    _tallies().append(tally)
    try:
        yield tally
    finally:
        _tallies().pop()


def _record(picks, slots: int) -> None:
    tallies = _tallies()
    if tallies:
        tally = tallies[-1]
        tally.picks = tally.picks + picks
        tally.slots += slots


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _full_reduce(op, x):
    return op(op(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _score_kernel(a_ref, b_ref, valid_ref, ucb_ref, score_ref, max_ref,
                  *, f: float, mode: str):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    ucb = ucb_ref[...].astype(jnp.float32)
    if mode == "eafl":
        reward = f * a + (1.0 - f) * b
    elif mode == "oort":
        reward = a
    elif mode == "eafl-epj":
        reward = a / jnp.maximum(b, 1e-3)
    else:
        raise ValueError(mode)
    reward = jnp.where(valid_ref[...] != 0, reward * (1.0 + ucb), SENTINEL)
    score_ref[...] = reward
    max_ref[...] = jnp.broadcast_to(_full_reduce(jnp.max, reward),
                                    max_ref.shape)


def _pick_kernel(t0_ref, score_ref, vals_ref, idx_ref, trips_ref, ties_ref,
                 *, k: int):
    r = score_ref[...]
    rows, lanes = r.shape
    # block-local position, exact in f32 (a block is far below 2**24)
    pos = (jax.lax.broadcasted_iota(jnp.int32, r.shape, 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, r.shape, 1)
           ).astype(jnp.float32)
    no_pos = jnp.float32(rows * lanes)
    slot = jax.lax.broadcasted_iota(jnp.int32, vals_ref.shape, 1)
    t0 = t0_ref[0]
    gt = jnp.sum((r > t0).astype(jnp.int32))
    eq = jnp.sum((r == t0).astype(jnp.int32))

    @pl.when(pl.program_id(0) == 0)
    def _():
        ties_ref[0] = 0

    before = ties_ref[0]      # entries equal to t0 in the earlier blocks
    trips = jnp.minimum(gt + jnp.minimum(eq, jnp.maximum(k - before, 0)), k)
    ties_ref[0] = jnp.minimum(before + eq, k)

    def pick(i, carry):
        r, v_out, p_out = carry
        m = _full_reduce(jnp.max, r)                                # (1, 1)
        j = _full_reduce(jnp.min, jnp.where(r == m, pos, no_pos))   # (1, 1)
        here = slot == i
        return (jnp.where(pos == j, NEG_INF, r),
                jnp.where(here, m, v_out),
                jnp.where(here, j, p_out))

    init = (r, jnp.full(vals_ref.shape, NEG_INF, jnp.float32),
            jnp.zeros(vals_ref.shape, jnp.float32))
    _, v_out, p_out = jax.lax.fori_loop(0, trips, pick, init)
    vals_ref[...] = v_out
    idx_ref[...] = p_out.astype(jnp.int32) + pl.program_id(0) * (rows * lanes)
    trips_ref[...] = jnp.full(trips_ref.shape, trips, jnp.int32)


def _pick(tiles, maxima, k: int, interpret: bool, index_offset):
    """Pass 2 and the merge over a padded ``(n_blocks * rows, 128)`` score
    row whose block maxima are ``maxima`` (n_blocks,)."""
    n_blocks = maxima.shape[0]
    rows = tiles.shape[0] // n_blocks
    if n_blocks >= k:
        t0 = jax.lax.top_k(maxima, k)[0][k - 1:]
    else:
        t0 = jnp.full((1,), NEG_INF, jnp.float32)
    k_pad = _round_up(k, LANES)
    row_spec = lambda width: pl.BlockSpec((None, 1, width),
                                          lambda i, t0: (i, 0, 0))
    vals, idx, trips = pl.pallas_call(
        functools.partial(_pick_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec((rows, LANES), lambda i, t0: (i, 0))],
            out_specs=[row_spec(k_pad), row_spec(k_pad), row_spec(LANES)],
            scratch_shapes=[pltpu.SMEM((1,), jnp.int32)]),
        # in block order: each block reads the ties of the ones before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, 1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 1, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(t0, tiles)

    # final merge: n_blocks*k candidates -> global top-k (exact)
    flat_v = vals[:, 0, :k].reshape(-1)
    flat_i = idx[:, 0, :k].reshape(-1)
    top_v, pos = jax.lax.top_k(flat_v, k)
    top_i = flat_i[pos]
    if index_offset is not None:
        top_i = top_i + jnp.asarray(index_offset, jnp.int32)
    _record(jnp.sum(trips[:, 0, 0]), n_blocks * k)
    return top_v, top_i


def _block_n(n: int, block_n: int) -> int:
    return min(_round_up(block_n, TILE), _round_up(n, TILE))


def topk_reward(a, b, valid, *, f: float, k: int,
                block_n: int = DEFAULT_BLOCK_N,
                ucb=None, mode: str = "eafl",
                interpret: bool = False, index_offset=None):
    """a/b: (N,) f32 score inputs (see module docstring per ``mode``);
    valid: (N,) int32/bool; ucb: optional (N,) f32 staleness bonus.
    Returns (vals, idx) each (k,). ``index_offset`` (static or traced
    scalar) shifts the returned indices — the sharded selection path uses
    this kernel as the per-shard leg of its tournament and passes the
    shard's global base index so candidates merge in global coordinates.
    ``block_n`` is rounded up to whole (8, 128) tiles."""
    assert mode in MODES, mode
    N = a.shape[0]
    if ucb is None:
        ucb = jnp.zeros((N,), jnp.float32)
    block_n = _block_n(N, block_n)
    # pad the tail block with masked entries so any N works
    pad = (-N) % block_n
    valid = valid.astype(jnp.int32)
    if pad:
        a = jnp.pad(a, (0, pad))
        b = jnp.pad(b, (0, pad))
        ucb = jnp.pad(ucb, (0, pad))
        valid = jnp.pad(valid, (0, pad))
    n_blocks = (N + pad) // block_n
    rows = block_n // LANES

    tiles = lambda x: x.reshape(-1, LANES)
    in_spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    score, maxima = pl.pallas_call(
        functools.partial(_score_kernel, f=f, mode=mode),
        grid=(n_blocks,),
        in_specs=[in_spec] * 4,
        out_specs=[in_spec,
                   pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks * rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(tiles(a), tiles(b), tiles(valid), tiles(ucb))
    return _pick(score, maxima[:, 0, 0], k, interpret, index_offset)


def topk_scores(x, k: int, *, block_n: int = DEFAULT_BLOCK_N,
                interpret: bool = False, index_offset=None):
    """``lax.top_k(x, k)`` of a finite (N,) f32 score row, index for index,
    through the pruned block kernel (exploration's ``where(unexplored,
    rank_bits, -1)``). Returns (vals, idx) each (k,)."""
    N = x.shape[0]
    block_n = _block_n(N, block_n)
    x = jnp.pad(x.astype(jnp.float32), (0, (-N) % block_n),
                constant_values=PAD)
    maxima = jnp.max(x.reshape(-1, block_n), axis=1)
    return _pick(x.reshape(-1, LANES), maxima, k, interpret, index_offset)
