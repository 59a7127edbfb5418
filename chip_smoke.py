"""Bring-up smoke run of the EAFL simulator on a TPU.

Drives the main path once through the entry points a user calls, at the
paper's full model width, and holds each device engine to its plain
reference in the same process:

  sync training   run_fl(engine="scanned") against run_fl(engine="host")
  async training  run_fl with buffer_size/max_concurrency (the device
                  event scan) against the host event loop
  selection       run_rounds at 1,048,576 clients, K = 100, through the
                  compiled Pallas top-k kernel, against the same run on
                  lax.top_k; and the kernel alone against lax.top_k

With ``--chips 4`` it runs only the comparisons that exist across chips:
the sharded selection engine at 4,194,304 clients against the
single-device scan, and sharded training against scanned training.

The training configuration is the paper's Sec. 5 speech workload at full
width (``paper_resnet_speech.CONFIG``, batch 20, lr 0.05, YoGi, 10 local
steps, K = 100) over FedScale's 2,618 Google Speech Commands clients with
64 synthetic samples each; weights and data come from seed 0.

Comparisons. Selected indices, ``cum_dropouts`` and ``participation``
must be identical (and for the sharded engine also ``round_duration``
and ``wall_hours``, as ``launch/sharded_check.py --train`` requires).
``mean_battery``, ``fairness`` and ``test_acc`` must agree within
``FLOAT_ATOL``, that check's limit, in every round, and so must
``train_loss`` in round 1, where both engines start from the same
parameters. From round 2 on ``train_loss`` must agree within
``TRAIN_LOSS_DRIFT_ATOL``. The engines run the same f32 arithmetic as
differently shaped programs (the host loop over the clients that
succeeded, the sharded engine over K / 4 slots per chip), and on the
TPU a cohort's local SGD is not bitwise invariant to that shape even
at highest precision. ``precision_probe.py`` measured it on a v5e:
the width split moves round-1 train_loss by 2.05e-05, but YoGi's first
step, -lr * d / (|d| + 1e-3), turns a last-bit difference into up to
1,000 times as much, and a run started one ulp away ends round 2 with
train_loss 1.26e-03 apart. The drift limit is twice that reading; a
bug of the kind it guards against (one shard's deltas left out of the
aggregate) moved train_loss by 0.42 in round 2 of a cut-down CPU run
(K = 8, 2 local steps).

Timings printed here are smoke timings of one cold process (compile
seconds, and rounds per second of a second, warm call that includes its
host-side setup), not benchmark results. Any failed check ends the run
with a traceback and a non-zero exit; the last line of a passing run is
one JSON object naming the device.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the sharded engines on four chips
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

FLOAT_ATOL = 5e-4
TRAIN_LOSS_DRIFT_ATOL = 2.5e-3
SELECTION_MODEL_BYTES = 85e6   # ResNet-34-class device workload
SELECTION_STEPS, SELECTION_BATCH = 400, 20


def train_config(**kw):
    from repro.configs import paper_resnet_speech
    from repro.core import SelectorConfig
    from repro.federated import FLConfig

    base = dict(selector=SelectorConfig(kind="eafl", k=100), n_clients=2618,
                rounds=3, local_steps=10, batch_size=20, client_lr=0.05,
                server_opt="yogi", samples_per_client=64,
                model=paper_resnet_speech.CONFIG)
    base.update(kw)
    return FLConfig(**base)


def check(ok, msg):
    """A failed check ends the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise AssertionError(msg)


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def report(label, rounds, cold_s, warm_s):
    print(f"  [smoke timing] {label}: compile ~{cold_s - warm_s:.1f} s, "
          f"steady {rounds / warm_s:.2f} rounds/s ({rounds} rounds in "
          f"{warm_s:.2f} s warm, {cold_s:.2f} s cold)", flush=True)


def compare_histories(label, ref, got, exact=("cum_dropouts",
                                              "participation")):
    """``exact`` fields identical; float statistics within the module's
    stated limits. Prints train_loss's difference per round and the
    largest difference of the other float fields."""
    import numpy as np

    check(len(ref.round) == len(got.round),
          f"{label}: {len(ref.round)} vs {len(got.round)} rounds")
    for f in exact:
        a, b = getattr(ref, f), getattr(got, f)
        check(a == b, f"{label}: {f} differs: {a} vs {b}")
    check(abs(ref.init_acc - got.init_acc) <= FLOAT_ATOL,
          f"{label}: init_acc {ref.init_acc} vs {got.init_acc}")
    diffs = {}
    for f in ("mean_battery", "fairness", "test_acc", "train_loss"):
        a = np.asarray(getattr(ref, f), np.float64)
        b = np.asarray(getattr(got, f), np.float64)
        atol = np.full(a.shape, FLOAT_ATOL)
        if f == "train_loss":
            atol[1:] = TRAIN_LOSS_DRIFT_ATOL
        d = np.abs(a - b)
        check(bool(np.all(d <= atol)),
              f"{label}: {f} |diff| per round {d.tolist()} over {atol}")
        diffs[f] = d
    print(f"  {label}: {', '.join(exact)} identical; max |diff|: "
          + ", ".join(f"{f} {np.max(d):.3g}" for f, d in diffs.items()
                      if f != "train_loss")
          + "; train_loss |diff| per round: "
          + ", ".join(f"{x:.3g}" for x in diffs["train_loss"]), flush=True)


def assert_same_history(label, a, b):
    check(a.as_dict() == b.as_dict(), f"{label}: repeat run differs")


# ------------------------------------------------------------ one chip
def phase_sync_training():
    from repro.federated import run_fl

    cfg = train_config()
    scanned, cold = timed(lambda: run_fl(cfg, engine="scanned"))
    again, warm = timed(lambda: run_fl(cfg, engine="scanned"))
    assert_same_history("sync scanned", scanned, again)
    report("sync training, scanned", cfg.rounds, cold, warm)
    host, host_s = timed(lambda: run_fl(cfg, engine="host"))
    print(f"  [smoke timing] sync training, host loop: {host_s:.2f} s cold",
          flush=True)
    compare_histories("sync host vs scanned", host, scanned)
    print(f"  final test_acc {scanned.test_acc[-1]:.4f}, train_loss "
          f"{scanned.train_loss[-1]:.4f}, participation "
          f"{scanned.participation}", flush=True)


def phase_async_training():
    import jax
    from repro.federated import (resolve_aggregation, resolve_train_engine,
                                 run_fl)

    cfg = train_config(rounds=5, buffer_size=20, max_concurrency=100)
    mode = resolve_aggregation("auto", cfg.buffer_size, cfg.max_concurrency)
    engine = resolve_train_engine(cfg.n_clients, jax.device_count(),
                                  mode=mode)
    check((mode, engine) == ("async", "scanned"),
          f"run_fl resolves to {mode}/{engine}")
    fused, cold = timed(lambda: run_fl(cfg))
    again, warm = timed(lambda: run_fl(cfg))
    assert_same_history("async scanned", fused, again)
    report("async training, event scan", cfg.rounds, cold, warm)
    host, host_s = timed(lambda: run_fl(cfg, engine="host"))
    print(f"  [smoke timing] async training, host event loop: {host_s:.2f} s "
          f"cold", flush=True)
    compare_histories("async host vs event scan", host, fused)


def fleet(key, n):
    """A fleet mid-run: 70% of clients explored with observed utility."""
    import jax
    from repro.core import make_population

    pop = make_population(key, n)
    ku, ke = jax.random.split(jax.random.fold_in(key, 1))
    return pop.replace(stat_util=jax.random.uniform(ku, (n,)) * 10,
                       explored=jax.random.bernoulli(ke, 0.7, (n,)))


def selection_args(n, k, rounds):
    import jax
    from repro.core import EnergyModel, SelectorConfig, SelectorState

    key = jax.random.PRNGKey(0)
    cfg = SelectorConfig(kind="eafl", k=k)
    return (jax.random.fold_in(key, 7), cfg, fleet(key, n),
            SelectorState.create(cfg), EnergyModel(), SELECTION_MODEL_BYTES,
            SELECTION_STEPS, SELECTION_BATCH, rounds)


def assert_same_selection(label, ref, got):
    import numpy as np

    for f in ("selected", "chosen", "total_dropped"):
        check(np.array_equal(np.asarray(ref[f]), np.asarray(got[f])),
              f"{label}: {f} differs")
    n = int(np.asarray(ref["chosen"]).sum())
    print(f"  {label}: {n} picks identical index for index", flush=True)


def tied_scores(key, n):
    """Scores on a dyadic grid: every product and sum is exact in f32, so
    any evaluation order gives the same values and thousands of exact
    ties straddle the top-k boundary."""
    import jax
    import jax.numpy as jnp

    level = lambda i, m: jax.random.randint(
        jax.random.fold_in(key, i), (n,), 0, m + 1).astype(jnp.float32) / m
    valid = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.7, (n,))
    return level(0, 64), level(1, 64), valid, level(2, 4) / 4


def phase_selection(n=1_048_576, k=100, rounds=5):
    import jax
    import numpy as np
    from repro.core.selection import PALLAS_N_THRESHOLD
    from repro.federated import run_rounds
    from repro.federated.simulation import _scanned_runner
    from repro.kernels import ops, ref

    check(n >= PALLAS_N_THRESHOLD, f"n={n} is below the Pallas threshold")
    args = selection_args(n, k, rounds)
    misses = _scanned_runner.cache_info().misses
    (_, _, traj), cold = timed(lambda: run_rounds(*args))
    check(traj["engine"] == "scanned", f"engine {traj['engine']}")
    check(_scanned_runner.cache_info().misses == misses + 1,
          "run_rounds did not build exactly one selection engine")
    # the one engine that call built is keyed on use_pallas=True,
    # interpret=False: looking that key up must hit the cache
    hits = _scanned_runner.cache_info().hits
    key, cfg, pop, state, em = args[:5]
    run = _scanned_runner(cfg, em, SELECTION_MODEL_BYTES, SELECTION_STEPS,
                          SELECTION_BATCH, None, None, True, False, None)
    check(_scanned_runner.cache_info().hits == hits + 1,
          "auto-dispatch did not pick the compiled Pallas kernel")
    hlo = run.lower(jax.random.split(key, rounds), pop,
                    state.canonical()).compile().as_text()
    check("tpu_custom_call" in hlo, "no Pallas kernel in the compiled step")
    print(f"  auto-dispatch: Pallas kernel, interpret=False, "
          f"tpu_custom_call in the compiled step", flush=True)
    (_, _, again), warm = timed(lambda: run_rounds(*args))
    assert_same_selection("selection repeat", traj, again)
    report(f"selection n={n:,} k={k}, Pallas", rounds, cold, warm)
    (_, _, xla), xla_cold = timed(lambda: run_rounds(*args, use_pallas=False))
    (_, _, _), xla_warm = timed(lambda: run_rounds(*args, use_pallas=False))
    report(f"selection n={n:,} k={k}, lax.top_k", rounds, xla_cold, xla_warm)
    assert_same_selection("Pallas vs lax.top_k run_rounds", xla, traj)

    a, b, valid, ucb = tied_scores(jax.random.fold_in(key, 11), n)
    tv, ti = ops.topk_reward(a, b, valid, f=0.25, k=k, ucb=ucb)
    ev, ei = ref.topk_reward_ref(a, b, valid, 0.25, k, ucb=ucb)
    check(np.array_equal(np.asarray(ti), np.asarray(ei)), "kernel indices")
    check(np.array_equal(np.asarray(tv), np.asarray(ev)), "kernel values")
    print(f"  kernel alone: top-{k} of {n:,} tied scores identical to "
          f"lax.top_k", flush=True)


# ---------------------------------------------------------- four chips
def phase_sharded_selection(n=4_194_304, k=100, rounds=5):
    import jax
    from repro.federated import run_rounds
    from repro.launch.mesh import make_client_mesh

    mesh = make_client_mesh()
    check(set(mesh.devices.flat) == set(jax.devices()),
          f"client mesh {mesh} does not span every chip")
    args = selection_args(n, k, rounds)
    (_, _, single), single_s = timed(lambda: run_rounds(*args,
                                                        mode="scanned"))
    (spop, _, sharded), cold = timed(lambda: run_rounds(*args, mesh=mesh))
    check(sharded["engine"] == "sharded", f"engine {sharded['engine']}")
    placed = spop.battery_pct.sharding.device_set
    check(placed == set(jax.devices()), f"population only on {placed}")
    (_, _, _), warm = timed(lambda: run_rounds(*args, mesh=mesh))
    report(f"sharded selection n={n:,} k={k} on {len(placed)} chips",
           rounds, cold, warm)
    print(f"  [smoke timing] single-device scan: {single_s:.2f} s cold",
          flush=True)
    assert_same_selection("sharded vs single-device", single, sharded)


def phase_sharded_training():
    from repro.federated import run_fl

    cfg = train_config()
    scanned = run_fl(cfg, engine="scanned")
    sharded, cold = timed(lambda: run_fl(cfg, engine="sharded"))
    _, warm = timed(lambda: run_fl(cfg, engine="sharded"))
    report("sharded training", cfg.rounds, cold, warm)
    compare_histories("sharded vs scanned training", scanned, sharded,
                      exact=("cum_dropouts", "participation",
                             "round_duration", "wall_hours"))


ONE_CHIP = (("sync training", phase_sync_training),
            ("async training", phase_async_training),
            ("selection at fleet scale", phase_selection))
FOUR_CHIPS = (("sharded selection", phase_sharded_selection),
              ("sharded training", phase_sharded_training))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded comparisons")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}; there is "
              f"no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s) are visible", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    for name, phase in ONE_CHIP if args.chips == 1 else FOUR_CHIPS:
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        phase()
        print(f"== {name}: passed ({time.perf_counter() - t0:.1f} s)",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
