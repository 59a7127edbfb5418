"""Readings that the correctness limits are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,3 [--variants high,stale_state,...] [--out FILE]

For each seed it builds the cell as a benchmark run does, makes the
set-up call (and the other checked calls) through the window's own front
door, and compares what the program produced with the plain reference:
the sound reading, which a limit has to clear. Each variant puts the
reference in the program's place, in a lower precision (``high`` for
training, ``bfloat16`` for selection: the control) or with a planted
fault (training: ``stale_state``, ``half_batch``, ``altered_answer``),
and gives a reading a limit has to catch. One process, so that set-up and
compilation are paid once. Prints one JSON line per seed.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="")
    ap.add_argument("--variant-seeds", type=int, default=3,
                    help="how many of the seeds also read the variants")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import run
    from chipbench.manifest import load_manifest, resolve

    cell = resolve(args.workload, load_manifest(ROOT), ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, run.CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from chipbench.drivers import make_driver
    from chipbench.execute import check_devices
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = check_devices(cell.chips)
    variants = [v for v in args.variants.split(",") if v]
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        driver = make_driver(cell, seed)
        for c in range(int(cell.traffic.get("checked_calls", 1))):
            driver.call(c)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        which = [None] + (variants if i < args.variant_seeds else [])
        readings = driver.check(cell.limits, which)
        row = {"seed": seed, "engine": driver.engine_used,
               "memory_peak_bytes": peak,
               "seconds": time.perf_counter() - t,
               "readings": {str(v) if v else "program":
                            {c["name"]: c["value"] for c in checks}
                            for v, checks in readings.items()}}
        print(json.dumps(row), flush=True)
        rows.append(row)
        del driver
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device":
                       devices[0].device_kind, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
