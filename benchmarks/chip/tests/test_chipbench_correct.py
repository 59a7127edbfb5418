"""The comparison that decides ``correct``, driven end to end on the CPU at
a size a test run holds: a sound run comes out correct; the control (the
plain reference in the program's place, one precision below what the
configuration states) and each fault the cell can have, planted in the
program underneath the timed path, come out not correct.

The look for a chip is replaced by the CPU's devices (and the chip's
peaks by none); everything else is a benchmark run: set-up call, window,
reference, limits from ``limits/<workload>.json``. The exchange between chips left out is
planted in the sharded engine that ``run_rounds`` picks on a four-chip
host; it needs four devices, so it runs in a child process with four
virtual CPU devices.
"""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import execute as ex
from chipbench import manifest

SEED = 2**33 + 977  # larger than 32 bits, as the driver's seeds are


def tiny_cell(workload: str):
    cell = manifest.resolve(workload, manifest.load_manifest())
    if cell.traffic["front_door"] == "run_fl":
        # the cell's own 10 local steps: they carry a last-bit difference
        # of the control's precision into the loss, as on the chip
        cell.traffic["selector"]["k"] = 6
        cell.traffic["fl_config"].update(n_clients=48, batch_size=8,
                                         samples_per_client=16,
                                         eval_samples=16)
        cell.config.update(width=8, blocks_per_stage=1, input_hw=8)
    else:
        cell.config["fleet"]["n_clients"] = 2048
        cell.traffic["selector"]["k"] = 16
        cell.traffic["rounds_per_call"] = 6
    return cell


@pytest.fixture(autouse=True)
def cpu_devices(monkeypatch):
    """The CPU's devices stand in for the chips; there are no peaks."""
    monkeypatch.setattr(ex, "check_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(ex, "peaks_for", lambda kind: None)


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    """The window may load programs from the persistent cache but compile
    none, as on the chip; the cache lives in a temporary directory."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])
    cc.reset_cache()


@pytest.fixture
def fresh_engines():
    """Engines are cached by configuration; a planted fault must be traced
    into a fresh one, and must not leak into the next test."""
    from repro.federated import server, simulation

    caches = (server._fused_runner, simulation._scanned_runner,
              simulation._sharded_scanned_runner)
    for c in caches:
        c.cache_clear()
    yield
    for c in caches:
        c.cache_clear()


def run(cell):
    result, checks = ex.execute(cell, SEED, 0.2, False, t0=time.time(),
                                log=lambda _: None)
    return result


CELLS = ["speech_sync_eafl", "reddit_select_eafl"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, fresh_engines):
    result = run(tiny_cell(workload))
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"


CONTROLS = {"speech_sync_eafl": "high", "reddit_select_eafl": "bfloat16"}


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload, fresh_engines):
    from chipbench import compare
    from chipbench.drivers import make_driver

    cell = tiny_cell(workload)
    driver = make_driver(cell, SEED)
    for c in range(int(cell.traffic.get("checked_calls", 1))):
        driver.call(c)
    readings = driver.check(cell.limits, (None, CONTROLS[workload]))
    assert compare.correct(readings[None]), readings[None]
    assert not compare.correct(readings[CONTROLS[workload]]), readings


# ------------------------------------------------ faults in the program
def _fleet_stale_state(monkeypatch):
    from repro.federated import simulation

    real = simulation.simulate_round_device
    monkeypatch.setattr(simulation, "simulate_round_device",
                        lambda pop, *a, **k: (pop, real(pop, *a, **k)[1]))


def _fleet_half_batch(monkeypatch):
    from repro.federated import simulation

    real = simulation.simulate_round_device

    def half(pop, *a, **k):
        new, out = real(pop, *a, **k)
        keep = jnp.arange(pop.n) < pop.n // 2
        return jax.tree.map(lambda n, o: jnp.where(keep, n, o), new,
                            pop), out

    monkeypatch.setattr(simulation, "simulate_round_device", half)


def _altered(real):
    def select(key, cfg, state, pop, *a, **k):
        idx, chosen, st = real(key, cfg, state, pop, *a, **k)
        return idx.at[0].set((idx[0] + 1) % pop.n), chosen, st
    return select


def _fleet_altered_answer(monkeypatch):
    from repro.federated import simulation

    monkeypatch.setattr(simulation, "_device_select",
                        _altered(simulation._device_select))


def _train_stale_state(monkeypatch):
    from repro.federated import server

    monkeypatch.setattr(server, "server_update",
                        lambda params, agg, opt, st: (params, st))


def _train_half_batch(monkeypatch):
    from repro.federated import server

    real = server.weighted_delta

    def half(deltas, w):
        return real(deltas, jnp.where(jnp.arange(w.shape[0])
                                      < (w.shape[0] + 1) // 2, w, 0.0))

    monkeypatch.setattr(server, "weighted_delta", half)


def _train_altered_answer(monkeypatch):
    from repro.federated import server

    monkeypatch.setattr(server, "_device_select",
                        _altered(server._device_select))


FAULTS = [
    ("speech_sync_eafl", _train_stale_state),
    ("speech_sync_eafl", _train_half_batch),
    ("speech_sync_eafl", _train_altered_answer),
    ("reddit_select_eafl", _fleet_stale_state),
    ("reddit_select_eafl", _fleet_half_batch),
    ("reddit_select_eafl", _fleet_altered_answer),
]


@pytest.mark.parametrize("workload,plant", FAULTS,
                         ids=[f"{w}-{p.__name__.split('_', 2)[2]}"
                              for w, p in FAULTS])
def test_fault_is_not_correct(workload, plant, monkeypatch, fresh_engines):
    plant(monkeypatch)
    result = run(tiny_cell(workload))
    assert not result["correct"], result["checks"]


# ------------------------------------------------ four-chip exchange
_CHILD = r"""
import os, sys, time
sys.path[:0] = sys.argv[1:3]
import jax
import jax.numpy as jnp
from repro.core import selection
from repro.federated import ENGINE_CUTOVER_N
from chipbench import execute as ex
from chipbench import manifest

# four virtual CPU devices stand in for the chips; there are no peaks
ex.check_devices = lambda chips: jax.devices()[:chips]
ex.peaks_for = lambda kind: None
jax.config.update("jax_compilation_cache_dir", sys.argv[3])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
# the selection mix on a four-chip host, at the smallest fleet run_rounds
# sends to the sharded engine
cell = manifest.resolve("reddit_select_eafl", manifest.load_manifest())
cell.chips = 4
cell.config["fleet"]["n_clients"] = ENGINE_CUTOVER_N
cell.traffic["selector"]["k"] = 16
cell.traffic["rounds_per_call"] = 6
if sys.argv[4] == "fault":
    def local_only(v_loc, i_loc, k, axis_name):
        # the exchange between chips left out: each shard keeps its own
        return i_loc[jax.lax.top_k(v_loc, k)[1]]
    selection._merge_candidates = local_only
result, _ = ex.execute(cell, %d, 0.2, False, t0=time.time(), log=print)
print("CORRECT", result["correct"])
""" % SEED


@pytest.mark.parametrize("mode,want", [("sound", True), ("fault", False)])
def test_sharded_exchange_fault(mode, want, tmp_path):
    from chipbench.manifest import BENCH_DIR, ROOT

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, os.path.join(ROOT, "src"), BENCH_DIR,
         str(tmp_path / "cache"), mode],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "engine sharded" in out.stdout, out.stdout
    assert f"CORRECT {want}" in out.stdout, out.stdout + out.stderr[-3000:]
