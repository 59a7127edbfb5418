"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and (with tracing) the per-layer metrics.

Set-up is everything from process start to the first timed call: imports,
reaching the chip, making the inputs from the seed and the traffic mix's
``setup_calls`` calls of the window's own front door with the window's
own shapes, which compile or load every program the window runs (a
chained call whose carried state is placed unlike fresh inputs runs a
program of its own). Those calls are the first the comparison checks.
The window then repeats calls until ``seconds`` have passed; a rate is
the work of all its calls over all its time. A compilation inside the
window ends the run without a result.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from typing import Dict, List, Tuple

import jax

from chipbench import compare, trace as tr
from chipbench.drivers import make_driver, span
from chipbench.manifest import Cell, metric_reader
from chipbench.peaks import peaks_for


class NoChip(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


class CompiledInWindow(RuntimeError):
    """Something compiled inside the measured window."""


def check_devices(chips: int) -> List:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devices[0].platform!r}; there "
                     f"is no CPU fallback")
    if len(devices) != chips:
        raise NoChip(f"the cell asks for {chips} chip(s), {len(devices)} "
                     f"are visible; the front doors dispatch on the device "
                     f"count, so it must match")
    return devices


class CompileWatch:
    """Counts, while armed, the XLA compilations JAX starts and how many of
    them the persistent cache served. A compilation the cache did not
    serve is one the window paid for."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.armed = False
        self.compiles = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_):
        if self.armed and event == self.COMPILE:
            self.compiles += 1

    def _on_event(self, event: str, **_):
        if self.armed and event == self.HIT:
            self.hits += 1

    @property
    def misses(self) -> int:
        return self.compiles - self.hits

    def close(self):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._on_time)
        monitoring.unregister_event_listener(self._on_event)


def _window(driver, first: int, seconds: float) -> Tuple[int, float]:
    calls, c = 0, first
    t = time.perf_counter()
    with span(tr.WINDOW_SPAN):
        while time.perf_counter() - t < seconds:
            with span("bench.call"):
                driver.call(c)
            calls, c = calls + 1, c + 1
        elapsed = time.perf_counter() - t
    return calls, elapsed


def _device_info(devices, memory_peak: int) -> Dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}


def execute(cell: Cell, seed: int, seconds: float, traced: bool, *,
            t0: float,
            log=lambda msg: print(msg, file=sys.stderr, flush=True)
            ) -> Tuple[Dict, List[dict]]:
    """Returns ``(result, checks)``: the result line's object and the
    numbers compared with their limits."""
    devices = check_devices(cell.chips)
    peaks = peaks_for(devices[0].device_kind)
    watch = CompileWatch()
    try:
        driver = make_driver(cell, seed)
        first = int(cell.traffic["setup_calls"])
        for c in range(first):
            driver.call(c)
        setup_s = time.time() - t0
        log(f"set-up {setup_s:.3f} s ({first} {driver.front_door} calls, "
            f"engine {driver.engine_used})")
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            watch.armed = True
            if traced:
                with tr.capture(trace_dir):
                    calls, elapsed = _window(driver, first, seconds)
            else:
                calls, elapsed = _window(driver, first, seconds)
            watch.armed = False
            if watch.misses:
                raise CompiledInWindow(
                    f"{watch.misses} XLA compilations inside the window")
            if watch.hits:
                log(f"window: {watch.hits} programs lowered again and "
                    f"loaded from the persistent cache")
            rounds = calls * driver.rounds_per_call
            log(f"window {elapsed:.3f} s: {calls} calls, {rounds} rounds")
            memory_peak = max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices)
            trace = tr.load(trace_dir) if traced else None
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    finally:
        watch.close()

    t = time.perf_counter()
    checks = driver.check(cell.limits)[None]
    log(f"reference and comparison {time.perf_counter() - t:.3f} s")

    result = {"correct": compare.correct(checks), "attempted": calls,
              "failed": 0}
    device = _device_info(devices, memory_peak)
    if not traced:
        # the traffic mix names the rate its calls' rounds make
        values = {"setup_s": setup_s,
                  cell.traffic["rate_metric"]: rounds / elapsed}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        device["busy_s"] = tr.busy_s(trace)
        device["window_s"] = trace.window_s
        ctx = {"trace": trace, "calls": calls, "rounds": rounds,
               "chips": len(devices), "config": cell.config,
               "traffic": cell.traffic, "work": driver.work_counts(),
               "peaks": peaks}
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = tr.breakdown(trace)
    result["device"] = device
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result, checks
