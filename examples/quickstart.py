"""Quickstart: EAFL vs Oort vs Random on the paper's battery-powered FL task.

The END-TO-END DRIVER for the paper's kind of system: real federated
training (ResNet on non-IID speech-like data, YoGi aggregation) under the
event-driven energy simulation. Defaults are CPU-sized; pass --rounds 150
--clients 200 for the paper-scale comparison in benchmarks/.

  PYTHONPATH=src python examples/quickstart.py [--rounds 30]
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.configs.paper_resnet_speech import reduced
from repro.core import SelectorConfig
from repro.federated import FLConfig, run_fl


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=60)
    ap.add_argument("--f", type=float, default=0.25, help="Eq.1 weight")
    args = ap.parse_args()

    results = {}
    for kind in ("eafl", "oort", "random"):
        cfg = FLConfig(
            selector=SelectorConfig(kind=kind, k=8, f=args.f),
            n_clients=args.clients, rounds=args.rounds, local_steps=6,
            batch_size=10, samples_per_client=48, eval_every=5,
            eval_samples=280, model=reduced(), input_hw=16,
            init_battery_low=8.0, init_battery_high=60.0)
        results[kind] = run_fl(cfg, verbose=False)
        h = results[kind]
        print(f"{kind:7s} acc={h.test_acc[-1]:.3f} "
              f"dropouts={h.cum_dropouts[-1]:3d} "
              f"fairness={h.fairness[-1]:.3f} "
              f"wall={h.wall_hours[-1]:.2f}h "
              f"participation={sum(h.participation)/len(h.participation):.2f}")

    e, o = results["eafl"], results["oort"]
    if o.cum_dropouts[-1] > 0:
        print(f"\nEAFL dropout reduction vs Oort: "
              f"{o.cum_dropouts[-1] / max(e.cum_dropouts[-1], 1):.2f}x "
              f"(paper reports up to 2.45x)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
