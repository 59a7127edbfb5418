"""Device idle time inside the program's ``fl.setup`` spans (``run_fl``'s
per-experiment set-up: data, model, fleet, cost table, runner and the
untrained eval), over the traced window, averaged over the chips."""
from chipbench.program_spans import idle_share


def read(ctx):
    return idle_share(ctx, "fl.setup")
