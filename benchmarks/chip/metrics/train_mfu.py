"""Model-FLOP utilization of the training step: the forward and backward
FLOPs of the experiments run in the traced window (``chipbench.work``,
evaluation included) over window x chips x the chip's bf16 peak."""


def read(ctx):
    flops = ctx["calls"] * ctx["work"]["flops_per_call"]
    window = ctx["trace"].window_s
    if flops <= 0 or window <= 0:
        return None
    return 100.0 * flops / (window * ctx["chips"]
                            * ctx["peaks"]["bf16_flops_per_s"])
