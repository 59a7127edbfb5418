"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig3a/3b/3c + fig4a/4b  the paper's evaluation (EAFL vs Oort vs Random)
                          at a CPU-sized scale (full scale: -m benchmarks.fl_comparison)
  kernels                 Pallas kernels vs jnp oracles
  roofline                summary of the dry-run roofline table (if present)

  PYTHONPATH=src python -m benchmarks.run [--rounds 40] [--clients 80]
"""
from __future__ import annotations

import argparse
import json
import os
import time


def fl_rows(rounds: int, clients: int):
    from benchmarks.fl_comparison import run_comparison, summarize

    t0 = time.perf_counter()
    results = run_comparison(rounds=rounds, clients=clients, fast=True)
    total_us = (time.perf_counter() - t0) * 1e6
    summary = summarize(results)
    rows = []
    per_sel_us = total_us / 3 / rounds
    for kind, s in summary.items():
        rows.append((f"fig3a_test_acc_{kind}", per_sel_us,
                     f"acc={s['final_acc']:.3f}"))
        rows.append((f"fig3b_train_loss_{kind}", per_sel_us,
                     f"loss={s['final_loss']:.3f}"))
        rows.append((f"fig3c_fairness_{kind}", per_sel_us,
                     f"jain={s['fairness']:.3f}"))
        rows.append((f"fig4a_dropouts_{kind}", per_sel_us,
                     f"cum={s['cum_dropouts']:.0f}"))
        rows.append((f"fig4b_round_duration_{kind}", per_sel_us,
                     f"mean_s={s['mean_round_s']:.0f}"))
    e, o = summary["eafl"], summary["oort"]
    rows.append(("headline_dropout_ratio", per_sel_us,
                 f"oort/eafl={o['cum_dropouts'] / max(e['cum_dropouts'], 1):.2f}x"))
    rows.append(("headline_acc_delta", per_sel_us,
                 f"eafl-oort={e['final_acc'] - o['final_acc']:+.3f}"))
    return rows


def roofline_rows():
    rows = []
    path = "experiments/dryrun_single.jsonl"
    if not os.path.exists(path):
        return rows
    with open(path) as f:
        recs = [json.loads(l) for l in f]
    for r in recs:
        name = f"roofline_{r['arch']}_{r['shape']}"
        t_total = max(r["t_compute"], r["t_memory"], r["t_collective"])
        rows.append((name, t_total * 1e6,
                     f"dominant={r['dominant']};useful={r['useful_ratio']:.2f}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    # 30 rounds x 100 clients: the smallest scale where dropouts do not
    # saturate (the paper-scale run lives in benchmarks.fl_comparison)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--skip-fl", action="store_true")
    args = ap.parse_args()

    rows = []
    if not args.skip_fl:
        rows += fl_rows(args.rounds, args.clients)
    from benchmarks.kernel_bench import bench_rows
    rows += bench_rows()
    rows += roofline_rows()

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
