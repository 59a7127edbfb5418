"""Share of the cohort local SGD slots trained in the window's ``run_fl``
calls whose delta reached an applied server update: the program's
``sgd.slots_aggregated`` over ``sgd.slots_trained``. The fused engines
train every slot of every round, dead ones too."""
from chipbench.program_spans import call_counts


def read(ctx):
    counts = call_counts(ctx)
    if not counts or not counts.get("sgd.slots_trained"):
        return None
    return (100.0 * counts.get("sgd.slots_aggregated", 0)
            / counts["sgd.slots_trained"])
