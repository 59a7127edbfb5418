"""Plain reference of a synchronous EAFL training experiment: the semantics
of ``repro.federated.run_fl`` for its first rounds.

Written from the paper's setup (EAFL Sec. 5: label-restricted non-IID
clients, local SGD, YoGi at the server) and the program's documented
semantics. It imports nothing of the program: the client data, the test
set, the initial weights and the fleet are made here from the
experiment's seed, by the stream definitions the program documents
(``PRNGKey(seed)`` split into population, data, model, test and loop
keys; each round splits the loop key into selection, training and
recharge keys). The model is the plain one of the configuration's family
(``reference/<family>.py``).

It reads the same settings the harness gives the program: the model, the
selector and the job's ``FLConfig`` fields. ``check_supported`` refuses
any setting it does not implement, so that a mix which asks the program
for more than the reference knows fails at set-up and not as an
incorrect run.

One departure from a per-client loop: the cohort's local SGD runs as one
``vmap`` over the clients that succeeded, the width the program trains
them at. On a TPU a convolution's result depends in the last bits on the
batch it is compiled in, and YoGi's first step turns such bits into
visible loss differences (``PERF.md``), so a width-1 loop would measure
that effect instead of the program.

``precision`` is that of every convolution and matrix product
(``reference.precision``): ``highest`` as the configuration states, or
``high`` for the control. ``fault`` plants one of the faults the
benchmark's comparison has to catch, for reading their numbers on the
chip (``calibrate.py``).
"""
from __future__ import annotations

import importlib
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from reference.eafl_round import (DEVICE_MIX, Selector, population,
                                  round_cost, select, selector_state,
                                  simulate)
from reference.precision import PRECISIONS, product

FAULTS = ("", "stale_state", "half_batch", "altered_answer")
# the job's FLConfig fields this reference implements; every other field
# keeps the program's default (no deadline, overcommit, faults,
# compression, recharging, proximal term, budget or async knobs)
FL_FIELDS = ("n_clients", "local_steps", "batch_size", "client_lr",
             "server_opt", "server_lr", "samples_per_client",
             "labels_per_client", "data_noise", "eval_every",
             "eval_samples", "init_battery_low", "init_battery_high",
             "idle_busy_fraction")
# run_fl's engines share one trajectory; the mode has to stay synchronous
RUN_FL_KWARGS = {"engine": ("auto", "host", "scanned", "sharded"),
                 "mode": ("auto", "sync")}


def model_module(model: dict):
    return importlib.import_module(f"reference.{model['family']}")


def check_supported(model: dict, selector: dict, fl: dict,
                    run_fl_kwargs: dict) -> None:
    """Raises ValueError for a setting this reference does not implement."""
    model_module(model).check(model)
    unknown = sorted(set(fl) - set(FL_FIELDS))
    bad = [f"FLConfig.{k}" for k in unknown]
    if fl.get("server_opt", "yogi") != "yogi":
        bad.append(f"server_opt={fl['server_opt']!r}")
    if selector.get("kind") != "eafl":
        bad.append(f"selector kind {selector.get('kind')!r}")
    bad += [f"selector.{k}" for k in sorted(set(selector)
                                            - set(Selector._fields))]
    for k, v in run_fl_kwargs.items():
        if v not in RUN_FL_KWARGS.get(k, ()):
            bad.append(f"run_fl({k}={v!r})")
    if bad:
        raise ValueError("reference fl_train does not implement "
                         + ", ".join(bad))


# ------------------------------------------------------------ data
def class_prototypes(key, n_classes: int, hw: int):
    """One smooth random image per class: a 6x6 mix of sine products."""
    k1, k2 = jax.random.split(key)
    n_freq = 6
    coef = jax.random.normal(k1, (n_classes, n_freq, n_freq, 1))
    phase = jax.random.uniform(k2, (n_classes, n_freq, n_freq, 2)) * 2 * jnp.pi
    xs = jnp.linspace(0, 1, hw)
    out = jnp.zeros((n_classes, hw, hw, 1))
    for fx in range(n_freq):
        for fy in range(n_freq):
            wave = (jnp.sin(2 * jnp.pi * (fx + 1) * xs[None, :, None]
                            + phase[:, fx, fy, 0][:, None, None])
                    * jnp.sin(2 * jnp.pi * (fy + 1) * xs[None, None, :]
                              + phase[:, fx, fy, 1][:, None, None]))
            out = out + coef[:, fx, fy, None, None, :] * wave[..., None]
    return out / n_freq


def _samples(key, labels, prototypes, noise):
    x = prototypes[labels]
    return (x + noise * jax.random.normal(key, x.shape)).astype(jnp.float32)


def client_data(key, n_clients, m, n_classes, labels_per_client, hw, noise):
    """Each client holds ``m`` samples of ``labels_per_client`` random
    labels (the paper's label-restricted partition)."""
    protos = class_prototypes(jax.random.PRNGKey(7), n_classes, hw)
    klab, _, knoise = jax.random.split(key, 3)

    def labels(k):
        perm = jax.random.permutation(k, n_classes)[:labels_per_client]
        return perm[jax.random.randint(jax.random.fold_in(k, 1), (m,), 0,
                                       labels_per_client)]

    y = jax.vmap(labels)(jax.random.split(klab, n_clients))
    x = jax.vmap(lambda k, yy: _samples(k, yy, protos, noise))(
        jax.random.split(knoise, n_clients), y)
    return x, y


# ------------------------------------------------------------ one round
@partial(jax.jit, static_argnames=("net", "steps", "batch", "lr", "prec"))
def local_sgd(net, params, xs, ys, keys, steps, batch, lr, prec):
    """Each client: ``steps`` SGD steps on batches drawn with replacement
    from its own data; returns its delta, its per-sample losses after
    training and its mean step loss."""
    def one(x, y, key):
        def step(p, k):
            i = jax.random.randint(k, (batch,), 0, x.shape[0])
            loss, g = jax.value_and_grad(
                lambda q: net.per_sample_loss(q, x[i], y[i], prec).mean())(p)
            return jax.tree.map(lambda w, d: w - lr * d, p, g), loss

        new, losses = jax.lax.scan(step, params, jax.random.split(key, steps))
        delta = jax.tree.map(lambda a, b: a - b, new, params)
        return delta, net.per_sample_loss(new, x, y, prec), losses.mean()

    return jax.vmap(one)(xs, ys, keys)


@partial(jax.jit, static_argnames=("lr", "prec", "fault"))
def aggregate(params, opt, deltas, weights, lr, prec, fault=""):
    """Weighted mean of the client deltas, applied by YoGi (b1 0.9, b2 0.99,
    eps 1e-3) with the negated mean as the pseudo-gradient."""
    if fault == "half_batch":
        weights = jnp.where(jnp.arange(weights.shape[0])
                            < (weights.shape[0] + 1) // 2, weights, 0.0)
    w = weights / jnp.maximum(weights.sum(), 1e-9)
    agg = jax.tree.map(lambda d: product(
        lambda a, b, q: jnp.tensordot(a, b, axes=1, precision=q), w, d, prec),
        deltas)
    t = opt["t"] + 1
    g = jax.tree.map(lambda d: -d, agg)
    m = jax.tree.map(lambda m_, g_: 0.9 * m_ + (1 - 0.9) * g_, opt["m"], g)
    v = jax.tree.map(
        lambda v_, g_: v_ - (1 - 0.99) * jnp.sign(v_ - g_ * g_) * (g_ * g_),
        opt["v"], g)
    bc1 = 1 - 0.9 ** t.astype(jnp.float32)
    bc2 = 1 - 0.99 ** t.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m_, v_: p + (-lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + 1e-3)),
        params, m, v)
    if fault == "stale_state":
        return params, opt
    return new, {"m": m, "v": v, "t": t}


# ------------------------------------------------------------ experiment
def run(model: dict, selector: dict, fl: dict, seed: int, rounds: int,
        precision: str = "highest", fault: str = "") -> Dict[str, List]:
    """The history fields of the first ``rounds`` rounds of the
    experiment with ``seed``, as ``FLHistory`` names them."""
    if fault not in FAULTS or precision not in PRECISIONS:
        raise ValueError(f"fault {fault!r}, precision {precision!r}")
    prec = precision
    net = model_module(model)
    sel = Selector(**selector)
    n = fl["n_clients"]
    kpop, kdata, kmodel, _ktest, kloop = jax.random.split(
        jax.random.PRNGKey(seed), 5)
    xs, ys = client_data(kdata, n, fl["samples_per_client"],
                         model["n_classes"], fl["labels_per_client"],
                         model["input_hw"], fl["data_noise"])
    params = net.init(kmodel, model)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    model_bytes = n_params * 4.0
    opt = {"m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params),
           "t": jnp.zeros((), jnp.int32)}
    fleet = population(kpop, n, DEVICE_MIX["category_probs"],
                       DEVICE_MIX["wifi_prob"], fl["init_battery_low"],
                       fl["init_battery_high"], fl["samples_per_client"])
    t_total, cost = round_cost(fleet, model_bytes, fl["local_steps"],
                               fl["batch_size"], model_bytes)
    st = selector_state(sel)
    hist = {k: [] for k in ("train_loss", "mean_battery", "round_duration",
                            "energy_spent_j", "participation", "fairness",
                            "cum_dropouts")}
    spent = jnp.float32(0.0)
    cum_drop = 0
    for _ in range(rounds):
        kloop, ksel, ktrain, _ = jax.random.split(kloop, 4)
        idx, chosen, st = select(ksel, sel, st, fleet, cost)
        if fault == "altered_answer":
            idx = idx.at[0].set((idx[0] + 1) % n)
        fleet, out = simulate(fleet, idx, chosen, t_total, cost, st["round"],
                              fl["idle_busy_fraction"])
        spent = spent + out["energy_spent_j"]
        cum_drop += int(out["new_dropouts"])
        succ = np.asarray(idx)[np.asarray(out["succeeded"])]
        deltas, per_sample, losses = local_sgd(
            net, params, xs[succ], ys[succ],
            jax.random.split(ktrain, len(succ)), fl["local_steps"],
            fl["batch_size"], fl["client_lr"], prec)
        finite = jnp.all(jnp.stack([
            jnp.all(jnp.isfinite(d.reshape(d.shape[0], -1)), axis=1)
            for d in jax.tree.leaves(deltas)]), axis=0)
        w = jnp.where(finite, fleet["n_samples"][succ].astype(jnp.float32),
                      0.0)
        deltas = jax.tree.map(lambda d: jnp.where(
            finite.reshape((-1,) + (1,) * (d.ndim - 1)), d, 0.0), deltas)
        params, opt = aggregate(params, opt, deltas, w, fl["server_lr"], prec,
                                fault)
        su = w * jnp.sqrt(jnp.mean(jnp.square(per_sample), axis=-1))
        fleet["stat_util"] = fleet["stat_util"].at[
            jnp.where(finite, succ, n)].set(su, mode="drop")
        x = fleet["times_selected"].astype(jnp.float32)
        s, s2 = jnp.sum(x), jnp.sum(jnp.square(x))
        hist["train_loss"].append(float(jnp.mean(losses)))
        hist["mean_battery"].append(float(out["mean_battery"]))
        hist["round_duration"].append(float(out["round_duration"]))
        hist["energy_spent_j"].append(float(spent))
        hist["participation"].append(
            float(len(succ) / max(int(np.sum(np.asarray(chosen))), 1)))
        hist["fairness"].append(float(jnp.where(
            s2 > 0, jnp.square(s) / (x.shape[0] * s2), 1.0)))
        hist["cum_dropouts"].append(cum_drop)
    return hist
