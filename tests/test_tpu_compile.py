"""The selection kernel compiles for a TPU v5e and stays exact.

Compile cases: the exploitation top-k (``topk_reward``) and the
exploration top-k (``topk_scores``) ahead-of-time compiled for one chip
of a described (not attached) v5e at the shapes the main path dispatches
to them — the million-client fleet, FedScale's Reddit fleet (1,660,820),
the Pallas threshold, an odd population (tail padding) and, for
exploitation, the per-shard leg of the sharded engine (4,194,304 clients
over four chips, traced ``index_offset``). The TPU compiler refuses
misaligned block shapes and over-budget VMEM that interpret mode
accepts, so these guard the chip path at no chip time.

Interpret cases: the same shapes run through the Pallas interpreter on
the CPU against ``lax.top_k`` over the unfused score (exploration: over
``where(valid, x, -1)``), with heavily tied inputs, index for index
(ties go to the lowest index).

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ref
from repro.kernels import topk_select as tk

# (n, k, index_offset, form): fleet, Pallas threshold, odd n, sharded
# leg, the Reddit fleet; the exploitation and the exploration top-k
SHAPES = [
    pytest.param(1_048_576, 100, None, "reward", id="fleet-1M-k100"),
    pytest.param(131_072, 10, None, "reward", id="threshold-131072-k10"),
    pytest.param(150_001, 10, None, "reward", id="odd-150001-k10"),
    pytest.param(1_048_576, 100, 3 * 1_048_576, "reward",
                 id="shard-leg-1M-k100"),
    pytest.param(1_660_820, 100, None, "reward", id="reddit-1660820-k100"),
    pytest.param(131_072, 10, None, "scores",
                 id="explore-threshold-131072-k10"),
    pytest.param(150_001, 10, None, "scores", id="explore-odd-150001-k10"),
    pytest.param(1_660_820, 100, None, "scores",
                 id="explore-reddit-1660820-k100"),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n,k,offset,form", SHAPES)
def test_topk_reward_compiles_for_v5e(n, k, offset, form, one_chip):
    def select(a, b, valid, ucb, base):
        return tk.topk_reward(a, b, valid, ucb=ucb, f=0.25, k=k,
                              index_offset=None if offset is None else base)

    vec = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    base = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if form == "reward":
        lowered = jax.jit(select).lower(
            vec(jnp.float32), vec(jnp.float32), vec(jnp.int32),
            vec(jnp.float32), base)
    else:
        lowered = jax.jit(lambda x: tk.topk_scores(x, k)).lower(
            vec(jnp.float32))
    assert "tpu_custom_call" in lowered.compile().as_text()


def _tied_inputs(n, seed=0):
    """Scores on a coarse grid (50 levels per input, 4 ucb levels), so
    thousands of exact ties straddle every top-k boundary."""
    key = jax.random.PRNGKey(seed)
    grid = lambda i, levels: jnp.round(jax.random.uniform(
        jax.random.fold_in(key, i), (n,)) * levels) / levels
    a, b, ucb = grid(0, 50), grid(1, 50), grid(2, 4) * 0.1
    valid = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.7, (n,))
    return a, b, valid, ucb


@pytest.mark.parametrize("n,k,offset,form", SHAPES)
def test_topk_reward_interpret_matches_lax_top_k(n, k, offset, form):
    a, b, valid, ucb = _tied_inputs(n)
    if form == "reward":
        run = jax.jit(lambda a, b, v, u: tk.topk_reward(
            a, b, v, ucb=u, f=0.25, k=k, interpret=True,
            index_offset=offset))
        tv, ti = run(a, b, valid, ucb)
        ev, ei = ref.topk_reward_ref(a, b, valid, 0.25, k, ucb=ucb)
    else:
        x = jnp.where(valid, a, -1.0)
        tv, ti = jax.jit(lambda x: tk.topk_scores(
            x, k, interpret=True, index_offset=offset))(x)
        ev, ei = jax.lax.top_k(x, k)
    shift = 0 if offset is None else offset
    np.testing.assert_array_equal(np.asarray(ti), np.asarray(ei) + shift)
    np.testing.assert_array_equal(np.asarray(tv), np.asarray(ev))
