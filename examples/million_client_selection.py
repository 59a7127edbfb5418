"""EAFL selection at production scale: the device-resident round engine
against a one-million-client population.

Four things are demonstrated and cross-checked:
  1. the fused Pallas top-k reward kernel against the jnp oracle;
  2. one full jitted selection step (``select_device``: scores + Gumbel
     exploration + state update) against the eager host reference;
  3. a multi-round ``lax.scan`` of the whole selection+energy+battery
     engine over the same population;
  4. the sharded engine (population split over a `clients` mesh,
     ``--devices D`` virtual CPU devices) against the single-device scan,
     index-for-index.

  PYTHONPATH=src python examples/million_client_selection.py [--n 65536]
  PYTHONPATH=src python examples/million_client_selection.py --devices 8
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.host_devices import force_host_device_count_from_argv

force_host_device_count_from_argv()  # must precede the first jax import

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (EnergyModel, SelectorConfig, SelectorState,
                        make_population, select, select_host)
from repro.federated import run_rounds_scanned
from repro.kernels import ops, ref


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_048_576,
                    help="population size (use e.g. 65536 for a CI smoke)")
    ap.add_argument("--k", type=int, default=100)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--devices", type=int, default=None,
                    help="virtual CPU device count for the sharded leg")
    args = ap.parse_args()
    N, K, F = args.n, min(args.k, args.n), 0.25
    key = jax.random.PRNGKey(0)

    # --- 1. fused kernel vs jnp oracle ---------------------------------
    util = jax.random.uniform(key, (N,))
    power = jax.random.uniform(jax.random.fold_in(key, 1), (N,))
    valid = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.97, (N,))

    t0 = time.time()
    ev, ei = ref.topk_reward_ref(util, power, valid, F, K)
    ev.block_until_ready()
    t_ref = time.time() - t0

    t0 = time.time()
    tv, ti = ops.topk_reward(util, power, valid, f=F, k=K,
                             block_n=min(65536, N))
    tv.block_until_ready()
    t_kernel = time.time() - t0

    # masked entries surface as a finite sentinel in the kernel vs -inf in
    # the oracle; compare the (normally: all) finite slots
    finite = jnp.isfinite(ev)
    assert jnp.allclose(tv[finite], ev[finite], atol=1e-6), "kernel != oracle"
    assert set(ti.tolist()) == set(ei.tolist())
    print(f"[kernel] selected {K} of {N:,} clients")
    print(f"[kernel] oracle  : {t_ref*1e3:8.1f} ms")
    print(f"[kernel] pallas  : {t_kernel*1e3:8.1f} ms (interpret mode on "
          f"CPU; TPU-native when backend=tpu)")

    # --- 2. full jitted selection step vs host reference ---------------
    pop = make_population(jax.random.fold_in(key, 3), N)
    ks = jax.random.split(jax.random.fold_in(key, 4), 2)
    pop = pop.replace(stat_util=jax.random.uniform(ks[0], (N,)) * 10,
                      explored=jax.random.bernoulli(ks[1], 0.7, (N,)))
    cfg = SelectorConfig(kind="eafl", k=K)
    state = SelectorState.create(cfg)
    pred = jnp.abs(jax.random.normal(jax.random.fold_in(key, 5), (N,))) * 5

    ksel = jax.random.fold_in(key, 6)
    select(ksel, cfg, state, pop, pred)       # compile + cache warmup
    select_host(ksel, cfg, state, pop, pred)  # eager-kernel cache warmup
    t0 = time.time()
    idx_dev, _ = select(ksel, cfg, state, pop, pred)
    t_dev = time.time() - t0
    t0 = time.time()
    idx_host, _ = select_host(ksel, cfg, state, pop, pred)
    t_host = time.time() - t0
    assert np.array_equal(idx_dev, idx_host), "device selection != host"
    print(f"[select] host    : {t_host*1e3:8.1f} ms")
    print(f"[select] jitted  : {t_dev*1e3:8.1f} ms "
          f"({t_host/max(t_dev,1e-9):.1f}x)")

    # --- 3. multi-round scanned engine ---------------------------------
    em = EnergyModel()
    t0 = time.time()
    fpop, fstate, traj = run_rounds_scanned(
        jax.random.fold_in(key, 7), cfg, pop, SelectorState.create(cfg),
        em, 85e6, 400, 20, rounds=args.rounds)
    jax.block_until_ready(traj["round_duration"])
    t_scan = time.time() - t0
    drop = int(traj["total_dropped"][-1])
    print(f"[scan]   {args.rounds} rounds over {N:,} clients in "
          f"{t_scan*1e3:.1f} ms (incl. compile); "
          f"final mean battery {float(fpop.battery_pct.mean()):.1f}%, "
          f"{drop:,} dropped")

    # --- 4. sharded engine vs the single-device scan --------------------
    from repro.federated import run_rounds_sharded
    from repro.launch.mesh import make_client_mesh

    mesh = make_client_mesh(args.devices)
    s = mesh.shape["clients"]
    t0 = time.time()
    spop, _, straj = run_rounds_sharded(
        jax.random.fold_in(key, 7), cfg, pop, SelectorState.create(cfg),
        em, 85e6, 400, 20, rounds=args.rounds, mesh=mesh)
    jax.block_until_ready(straj["round_duration"])
    t_shard = time.time() - t0
    assert np.array_equal(np.asarray(traj["selected"]),
                          np.asarray(straj["selected"])), \
        "sharded selection trajectory != single-device"
    assert np.array_equal(np.asarray(traj["chosen"]),
                          np.asarray(straj["chosen"]))
    print(f"[shard]  same {args.rounds} rounds on a {s}-shard `clients` "
          f"mesh in {t_shard*1e3:.1f} ms (incl. compile); selection "
          f"trajectory identical index-for-index")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
