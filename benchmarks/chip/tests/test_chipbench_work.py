"""Work counts against what XLA counts: each convolution's FLOPs against
``cost_analysis()`` of that convolution alone, the configuration's
model FLOPs (from its family's counts) against a client step compiled on
the CPU, and the selection round's least bytes against the population
leaves' ``nbytes``."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import work
from chipbench.manifest import BENCH_DIR, module


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


def _xla_flops(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().cost_analysis()["flops"]


@pytest.mark.parametrize("size,k,stride,cin,cout", [
    (32, 3, 1, 1, 16), (32, 3, 2, 16, 32), (16, 1, 2, 16, 32),
    (8, 3, 1, 64, 64), (7, 3, 2, 8, 8)])
def test_conv_flops_match_xla(size, k, stride, cin, cout):
    x = jax.ShapeDtypeStruct((1, size, size, cin), jnp.float32)
    w = jax.ShapeDtypeStruct((k, k, cin, cout), jnp.float32)
    conv = lambda a, b: jax.lax.conv_general_dilated(
        a, b, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert work.conv_flops(size, k, stride, cin, cout) == _xla_flops(
        conv, x, w)


def test_resnet_flops_bound_a_client_step():
    m = _config("cifar_resnet20_gn")
    family, net = module("families", m["family"]), module("reference",
                                                          m["family"])
    batch = 20
    p = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0), m))
    x = jax.ShapeDtypeStruct((batch, m["input_hw"], m["input_hw"],
                              m["in_channels"]), jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)

    def fwd(p, x, y):
        return net.per_sample_loss(p, x, y, "highest")

    def step(p, x, y):
        g = jax.grad(lambda q: fwd(q, x, y).mean())(p)
        return jax.tree.map(lambda a, b: a - 0.05 * b, p, g)

    # XLA also counts the elementwise work (GroupNorm, ReLU, the loss, the
    # update) that model FLOPs leave out: a few percent on top
    for fn, ours in ((fwd, family.forward_flops(m)),
                     (step, family.train_flops(m))):
        xla = _xla_flops(fn, p, x, y)
        assert batch * ours <= xla <= 1.10 * batch * ours


def test_training_flops_per_experiment_counts_rounds_and_evals():
    m = _config("cifar_resnet20_gn")
    path = os.path.join(BENCH_DIR, "traffic", "speech_sync_eafl.json")
    with open(path) as f:
        t = json.load(f)
    family, fl, k = module("families", m["family"]), t["fl_config"], 100
    fwd, train = family.forward_flops(m), family.train_flops(m)
    per_round = k * (fl["local_steps"] * fl["batch_size"] * train
                     + fl["samples_per_client"] * fwd)
    got = work.training_flops_per_experiment(family, m, k, fl, 5)
    # eval_every 3: the untrained model, round 3 and round 5 are evaluated
    assert fl["eval_every"] == 3
    assert got == 5 * per_round + 3 * fl["eval_samples"] * fwd


def test_selection_bytes_match_population_leaves():
    from repro.core import make_population

    n = 4096
    pop = make_population(jax.random.PRNGKey(0), n)
    for f, per in work.POPULATION_LEAF_BYTES.items():
        assert getattr(pop, f).nbytes == n * per, f
    want = (sum(getattr(pop, f).nbytes for f in work.READ_LEAVES)
            + pop.battery_pct.nbytes  # one float32 predicted cost each
            + sum(getattr(pop, f).nbytes for f in work.WRITE_LEAVES))
    assert work.selection_bytes_per_round(work.POPULATION_LEAF_BYTES,
                                          n) == want
