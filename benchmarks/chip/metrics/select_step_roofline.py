"""Roofline share of the whole selection step (scoring, top-k, energy
simulation): the least HBM traffic a round must make over the fleet
(``chipbench.work.selection_bytes_per_round``) at the chips' HBM peak,
over the device busy time per round. Memory-bound by construction: a
round does O(1) arithmetic per byte."""
from chipbench.trace import busy_s


def read(ctx):
    rounds = ctx["rounds"]
    busy = busy_s(ctx["trace"])
    if rounds <= 0 or busy <= 0:
        return None
    least = ctx["work"]["bytes_per_round"] / (
        ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (busy / rounds)
