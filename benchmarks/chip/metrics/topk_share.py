"""Share of device busy time in what computes the top-k: the Pallas
kernel, XLA's top-k and sort operations, and the final merge."""
from chipbench.trace import is_topk, share


def read(ctx):
    s = share(ctx["trace"], is_topk)
    return None if s is None else 100.0 * s
