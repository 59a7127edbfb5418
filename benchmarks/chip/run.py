"""Chip benchmark of the EAFL simulator: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Resolves the cell by name through ``BENCHMARK.json``, makes its inputs
from the seed, warms up the cell's own shapes (set-up), calls the public
front door (``run_fl`` or ``run_rounds``) for ``--seconds``, and checks
what the timed path produced against the plain reference under
``reference/``. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics read from
a profiler trace of the window), ``device``, with ``--trace 1`` a
``breakdown``, and last the ``checks``: each number compared with its
limit, which also end standard error.

Needs a TPU with exactly the cell's number of chips; anywhere else it
exits with status 2 and prints no result. JAX's persistent compilation
cache lives in ``.bench_jax_cache/`` at the root of the checkout.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
# a directory of the benchmark's own, so that no cache the program or a
# tool keeps in the checkout's .jax_cache/ is mixed into it
CACHE_DIR = ".bench_jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.manifest import load_manifest, resolve

    cell = resolve(args.workload, load_manifest(ROOT), ROOT)
    # the cache directory is part of the checkout, at a fixed path, and is
    # the one the program's own entry points then use
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, CACHE_DIR)
    # every program, however quick to compile, so that a warm run compiles
    # nothing at all
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from chipbench.execute import NoChip, execute
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        result, checks = execute(cell, args.seed, args.seconds,
                                 bool(args.trace), t0=T0)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for c in checks:
        ok = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
