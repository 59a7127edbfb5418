"""EAFL reward + top-k client selection Pallas kernel (TPU target).

The paper's selection at production scale: for millions of registered
clients, fuse the selection score with a blocked top-k reduction so the
million-entry reward vector is never materialised in HBM. Each grid step
processes one VMEM-sized block of clients and emits that block's local
top-k (values + global indices) via K iterations of max+mask; the host
merges nblocks*k candidates with one tiny final top_k — an exact two-level
tournament.

Three fused score variants (``mode``), all multiplied by the Oort/EAFL
UCB staleness bonus ``(1 + ucb)`` and masked to ``-inf`` outside ``valid``:

  eafl      f*a + (1-f)*b          (Eq. 1: a=norm. utility, b=norm. power)
  oort      a                      (a = Oort utility, Eq. 2)
  eafl-epj  a / max(b, 1e-3)       (a = utility, b = predicted %-battery)

Arbitrary population sizes are supported: the tail block is padded with
``valid=0`` entries. Masked entries score a finite ``SENTINEL`` (not
``-inf``) so that when ``k`` exceeds a block's valid count the repeated
max still walks distinct, lowest-index-first candidates — matching
``lax.top_k`` tie-breaking — instead of re-emitting index 0. Sentinel
picks therefore surface with value ``SENTINEL`` where the jnp oracle
reports ``-inf``; they are never preferred over any valid candidate.

TPU layout: the (N,) inputs are viewed as ``(N/128, 128)`` so every block
is a whole number of (8, 128) f32 tiles. Inside a block the k picks use
only full-block reductions and iota compares (max, then the lowest
position holding it, then mask that position): no dynamic vector index or
update. Each block's k picks collect into one lane-dense ``(1, k_pad)``
row, written once. Grid: (n_blocks,); VMEM per program: 4 input blocks
(16 KiB each at the default block) + two ``k_pad``-wide output rows.
Scores must be finite or ``-inf`` (they are: every mode's inputs are).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 4096
NEG_INF = -jnp.inf
SENTINEL = -3e38          # masked-entry score: below any real reward, > -inf
MODES = ("eafl", "oort", "eafl-epj")
LANES = 128
TILE = 8 * LANES          # one f32 (8, 128) tile: the block-size quantum


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _topk_kernel(a_ref, b_ref, valid_ref, ucb_ref, vals_ref, idx_ref,
                 *, f: float, k: int, mode: str):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    valid = valid_ref[...] != 0
    ucb = ucb_ref[...].astype(jnp.float32)
    if mode == "eafl":
        reward = f * a + (1.0 - f) * b
    elif mode == "oort":
        reward = a
    elif mode == "eafl-epj":
        reward = a / jnp.maximum(b, 1e-3)
    else:
        raise ValueError(mode)
    reward = jnp.where(valid, reward * (1.0 + ucb), SENTINEL)

    rows, lanes = reward.shape
    # block-local position, exact in f32 (a block is far below 2**24)
    pos = (jax.lax.broadcasted_iota(jnp.int32, reward.shape, 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, reward.shape, 1)
           ).astype(jnp.float32)
    no_pos = jnp.float32(rows * lanes)
    slot = jax.lax.broadcasted_iota(jnp.int32, vals_ref.shape, 1)

    def full_reduce(op, x):
        return op(op(x, axis=1, keepdims=True), axis=0, keepdims=True)

    def pick(i, carry):
        r, v_out, p_out = carry
        m = full_reduce(jnp.max, r)                                # (1, 1)
        j = full_reduce(jnp.min, jnp.where(r == m, pos, no_pos))   # (1, 1)
        here = slot == i
        return (jnp.where(pos == j, NEG_INF, r),
                jnp.where(here, m, v_out),
                jnp.where(here, j, p_out))

    init = (reward, jnp.full(vals_ref.shape, NEG_INF, jnp.float32),
            jnp.zeros(vals_ref.shape, jnp.float32))
    _, v_out, p_out = jax.lax.fori_loop(0, k, pick, init)
    vals_ref[...] = v_out
    idx_ref[...] = p_out.astype(jnp.int32) + pl.program_id(0) * (rows * lanes)


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
    block_n = min(_round_up(block_n, TILE), _round_up(N, TILE))
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
    k_pad = _round_up(k, LANES)

    tiles = lambda x: x.reshape(-1, LANES)
    kernel = functools.partial(_topk_kernel, f=f, k=k, mode=mode)
    in_spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    out_spec = pl.BlockSpec((None, 1, k_pad), lambda i: (i, 0, 0))
    vals, idx = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[in_spec] * 4,
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, 1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 1, k_pad), jnp.int32),
        ],
        interpret=interpret,
    )(tiles(a), tiles(b), tiles(valid), tiles(ucb))

    # final merge: nblocks*k candidates -> global top-k (exact)
    flat_v = vals[:, 0, :k].reshape(-1)
    flat_i = idx[:, 0, :k].reshape(-1)
    top_v, pos = jax.lax.top_k(flat_v, k)
    top_i = flat_i[pos]
    if index_offset is not None:
        top_i = top_i + jnp.asarray(index_offset, jnp.int32)
    return top_v, top_i
