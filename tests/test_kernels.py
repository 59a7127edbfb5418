"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import topk_select as tk


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("shape", [
    (1, 2, 128, 64), (2, 4, 256, 64), (1, 2, 512, 128), (2, 1, 256, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(shape, dtype, causal, rng):
    B, H, S, D = shape
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), shape, dtype)
               for i in range(3))
    out = ops.flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    exp = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("blocks", [(64, 128), (128, 64), (256, 256)])
def test_flash_attention_block_sweep(blocks, rng):
    bq, bk = blocks
    B, H, S, D = 1, 2, 256, 64
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (B, H, S, D))
               for i in range(3))
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    exp = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


# ------------------------------------------------------------ selective scan
@pytest.mark.parametrize("shape", [(1, 32, 64, 8), (2, 64, 128, 16),
                                   (1, 128, 256, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan(shape, dtype, rng):
    B, S, di, ds = shape
    x = jax.random.normal(jax.random.fold_in(rng, 0), (B, S, di), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(rng, 1),
                                           (B, S, di), dtype))
    Bm = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, ds), dtype)
    Cm = jax.random.normal(jax.random.fold_in(rng, 3), (B, S, ds), dtype)
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(rng, 4), (di, ds)))
    D = jnp.ones((di,))
    out = ops.selective_scan(x, dt, Bm, Cm, A, D, block_d=di // 2)
    exp = ref.selective_scan_ref(x, dt, Bm, Cm, A, D)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


# ------------------------------------------------------------- top-k reward
def _topk_inputs(case, n, key):
    """``(a, b, valid)`` for a top-k case: iid normals; a coarse grid
    whose ties straddle the pruning threshold; the whole top-k in one
    block (the first 1024 entries hold every high score); fewer valid
    entries than k (five); none valid at all, as in a fleet whose
    batteries have all run out."""
    normal = lambda i: jax.random.normal(jax.random.fold_in(key, i), (n,))
    grid = lambda i: jnp.round(jax.random.uniform(
        jax.random.fold_in(key, i), (n,)) * 8) / 8
    valid = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.8, (n,))
    if case == "iid":
        return normal(0), normal(1), valid
    if case == "grid":
        return grid(0), grid(1), valid
    if case == "one-block":
        hot = jnp.arange(n) < 1024
        return jnp.where(hot, 10.0 + normal(0), normal(0)), normal(1), valid
    if case == "few-valid":
        few = jax.random.permutation(jax.random.fold_in(key, 3), n)[:5]
        return normal(0), normal(1), jnp.zeros((n,), bool).at[few].set(True)
    if case == "none-valid":
        return normal(0), normal(1), jnp.zeros((n,), bool)
    raise ValueError(case)


# (n, k, block, inputs, form, picks): blocks round up to 1024 entries;
# picks "all" = n_blocks * k, the unpruned loop; "pruned" = under 5% of
# that; "k+valid" = the valid entries and k masked ones at most
TOPK_CASES = [
    pytest.param(1024, 10, 256, "iid", "reward", "all", id="1024-10-256"),
    pytest.param(4096, 32, 1024, "iid", "reward", "all",  # n_blocks < k
                 id="4096-32-1024"),
    pytest.param(2048, 1, 512, "iid", "reward", None, id="2048-1-512"),
    pytest.param(8192, 64, 4096, "iid", "reward", "all", id="8192-64-4096"),
    pytest.param(65536, 16, 1024, "grid", "reward", None,
                 id="ties-straddle-t0"),
    pytest.param(65536, 16, 1024, "one-block", "reward", None,
                 id="topk-in-one-block"),
    pytest.param(65536, 16, 1024, "few-valid", "reward", "k+valid",
                 id="fewer-valid-than-k"),
    pytest.param(65536, 16, 1024, "none-valid", "reward", "k+valid",
                 id="none-valid"),
    pytest.param(1_048_576, 100, 4096, "iid", "reward", "pruned",
                 id="iid-1M-k100"),
    pytest.param(65536, 16, 1024, "grid", "scores", None,
                 id="exploration-ties"),
    pytest.param(1_048_576, 100, 4096, "iid", "scores", "pruned",
                 id="exploration-iid-1M-k100"),
]


@pytest.mark.parametrize("n,k,block,inputs,form,picks", TOPK_CASES)
def test_topk_reward(n, k, block, inputs, form, picks, rng):
    """The pruned block top-k against ``lax.top_k``, index for index
    (ties to the lowest index): the fused reward, or the exploration
    form over ``where(valid, x, -1)``; and the serial picks it made."""
    util, power, valid = _topk_inputs(inputs, n, rng)
    x = jnp.where(valid, util, -1.0)

    def run(util, power, valid, x):
        with tk.pick_tally() as tally:
            if form == "reward":
                out = tk.topk_reward(util, power, valid, f=0.25, k=k,
                                     block_n=block, interpret=True)
            else:
                out = tk.topk_scores(x, k, block_n=block, interpret=True)
        return out + (tally.picks, tally.slots)

    tv, ti, made, slots = (int(o) if o.ndim == 0 else np.asarray(o)
                           for o in jax.jit(run)(util, power, valid, x))
    if form == "reward":
        ev, ei = ref.topk_reward_ref(util, power, valid, 0.25, k)
    else:
        ev, ei = jax.lax.top_k(x, k)
    np.testing.assert_array_equal(ti, np.asarray(ei))
    # masked picks read SENTINEL where the oracle reads -inf
    ev = np.asarray(ev)
    np.testing.assert_array_equal(tv[np.isfinite(ev)], ev[np.isfinite(ev)])
    assert slots == -(-n // max(block, 1024)) * k
    if picks == "all":
        assert made == slots
    elif picks == "pruned":
        assert made < 0.05 * slots
    elif picks == "k+valid":
        # masked entries are picked in the first block only
        assert k <= made <= k + int(jnp.sum(valid))
    if inputs == "one-block":
        # the hot block holds ~800 entries above t0 but makes k picks;
        # the k - 1 next blocks by maximum make at least one each
        assert 2 * k - 1 <= made < 4 * k


def test_topk_reward_f_extremes(rng):
    """f=1 ranks by util alone; f=0 by power alone (Eq. 1 semantics)."""
    n = 512
    util = jax.random.normal(jax.random.fold_in(rng, 0), (n,))
    power = jax.random.normal(jax.random.fold_in(rng, 1), (n,))
    valid = jnp.ones((n,), bool)
    _, ti_u = ops.topk_reward(util, power, valid, f=1.0, k=5, block_n=256)
    assert set(np.asarray(ti_u).tolist()) == \
        set(np.asarray(jax.lax.top_k(util, 5)[1]).tolist())
    _, ti_p = ops.topk_reward(util, power, valid, f=0.0, k=5, block_n=256)
    assert set(np.asarray(ti_p).tolist()) == \
        set(np.asarray(jax.lax.top_k(power, 5)[1]).tolist())


# --------------------------------------------------------------- ssd chunk
@pytest.mark.parametrize("shape", [(1, 64, 4, 16, 8), (2, 128, 8, 32, 16),
                                   (1, 256, 4, 64, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk(shape, dtype, rng):
    B, S, nh, hd, ds = shape
    x = jax.random.normal(jax.random.fold_in(rng, 0), (B, S, nh, hd), dtype)
    Bm = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, ds), dtype)
    Cm = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, ds), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(rng, 3),
                                           (B, S, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(rng, 4), (nh,)))
    out = ops.ssd_chunk(x, Bm, Cm, dt, A, chunk=min(64, S), block_h=min(4, nh))
    exp = ref.ssd_chunk_ref(x, Bm, Cm, dt, A)
    tol = 5e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


def test_ssd_chunk_matches_model_path(rng):
    """The Pallas SSD kernel agrees with the model's chunked-jnp SSD math
    (both against the sequential oracle, so transitively each other)."""
    B, S, nh, hd, ds = 1, 128, 4, 32, 16
    x = jax.random.normal(rng, (B, S, nh, hd))
    Bm = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, ds))
    Cm = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, ds))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(rng, 3), (B, S, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(rng, 4), (nh,)))
    out = ops.ssd_chunk(x, Bm, Cm, dt, A, chunk=32, block_h=2)
    exp = ref.ssd_chunk_ref(x, Bm, Cm, dt, A)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=5e-4)
