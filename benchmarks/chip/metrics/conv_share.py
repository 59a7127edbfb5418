"""Share of device busy time in convolutions and the fusions that hold one
(cohort local SGD and evaluation of the ResNet)."""
from chipbench.trace import is_convolution, share


def read(ctx):
    s = share(ctx["trace"], is_convolution)
    return None if s is None else 100.0 * s
