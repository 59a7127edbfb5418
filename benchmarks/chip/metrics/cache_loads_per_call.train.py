"""Programs XLA was asked to build inside the window's ``run_fl`` calls,
compiled or loaded from the persistent compilation cache (the program's
``xla.programs`` count), per call. A warm window compiles nothing, so
each is a program lowered again and loaded from the cache."""
from chipbench.program_spans import call_counts


def read(ctx):
    counts = call_counts(ctx)
    if counts is None:
        return None
    return counts.get("xla.programs", 0) / ctx["calls"]
