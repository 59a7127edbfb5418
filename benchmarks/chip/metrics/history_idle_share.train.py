"""Device idle time inside the program's ``fl.history`` spans (``run_fl``
turning the fused trajectory into its history on the host), over the
traced window, averaged over the chips."""
from chipbench.program_spans import idle_share


def read(ctx):
    return idle_share(ctx, "fl.history")
