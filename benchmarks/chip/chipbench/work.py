"""Work counts the per-layer metrics divide by, computed from a
configuration's shapes alone.

``conv_flops``: multiply-adds of a convolution, taps that fall on
``SAME`` padding left out, at 2 FLOPs each. A model family
(``families/<family>.py``) sums them into its forward and training FLOPs
per sample; elementwise work (norms, activations, the loss) is not
counted, as model-FLOP utilization leaves it out.

``training_flops_per_experiment``: what one synchronous ``run_fl``
experiment asks of the model, from those counts and the job's sizes.

``selection_bytes_per_round``: the least HBM traffic a selection round
must make over the fleet, from the population leaves it has to read and
write. Every client's score needs its battery, statistical utility, last
duration, explored and dropped flags, last round and predicted cost (one
float32: the smallest form the cost can take); every client's battery
drains and any client can drop, so the battery and dropped leaves are
written back. The chosen clients' other updates touch ``k`` entries and
are not counted.
"""
from __future__ import annotations

from typing import Dict

READ_LEAVES = ("battery_pct", "stat_util", "last_duration", "explored",
               "last_round", "dropped")
WRITE_LEAVES = ("battery_pct", "dropped")
COST_BYTES_PER_CLIENT = 4


def _taps(size: int, k: int, stride: int) -> int:
    """Kernel taps that land inside the input, summed over the output
    positions of one dimension of a ``SAME``-padded convolution: taps on
    the padding multiply zeros and are no work the model needs."""
    out = -(-size // stride)
    pad_lo = max((out - 1) * stride + k - size, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * stride + t - pad_lo < size)


def conv_flops(size_in: int, k: int, stride: int, cin: int, cout: int
               ) -> int:
    """FLOPs of a ``SAME``-padded square convolution over one sample, at 2
    per multiply-add."""
    return 2 * _taps(size_in, k, stride) ** 2 * cin * cout


def training_flops_per_experiment(family, model: Dict, k: int, fl: Dict,
                                  rounds: int) -> float:
    """Model FLOPs of one ``run_fl`` experiment of ``rounds`` rounds: each
    round trains every one of the ``k`` cohort slots for ``local_steps``
    batches (forward and backward) and then scores each client's whole
    local set (forward); the untrained model, every ``eval_every``-th
    round and the last round are evaluated on the test set."""
    fwd = family.forward_flops(model)
    train = family.train_flops(model)
    per_round = k * (fl["local_steps"] * fl["batch_size"] * train
                     + fl["samples_per_client"] * fwd)
    evals = 1 + sum(1 for r in range(1, rounds + 1)
                    if r % fl["eval_every"] == 0 or r == rounds)
    return rounds * per_round + evals * fl["eval_samples"] * fwd


def selection_bytes_per_round(leaf_bytes: Dict[str, int], n: int) -> int:
    """``leaf_bytes``: bytes per client of each population leaf."""
    return (n * sum(leaf_bytes[f] for f in READ_LEAVES)
            + n * COST_BYTES_PER_CLIENT
            + n * sum(leaf_bytes[f] for f in WRITE_LEAVES))


# bytes per client of the population leaves (repro.core.ClientPopulation)
POPULATION_LEAF_BYTES = {
    "category": 4, "network": 4, "down_mbps": 4, "up_mbps": 4,
    "battery_pct": 4, "stat_util": 4, "last_duration": 4, "explored": 1,
    "last_round": 4, "times_selected": 4, "dropped": 1, "n_samples": 4}
