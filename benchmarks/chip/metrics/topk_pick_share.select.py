"""Share of the Pallas top-k's serial picks that the window's
``run_rounds`` calls made: the program's ``topk.picks`` over its
``topk.pick_slots`` (what the unpruned kernel makes, n_blocks x k per
top-k call), summed over the newest ``ctx["calls"]`` top-level
``run_rounds`` spans of the program's record. Every call of a selection
cell has one shape, so no pairing with the harness's spans is needed.
None where no such span counted a slot: a program without the counts,
or a top-k that ran on ``lax.top_k``."""


def read(ctx, records=None):
    if records is None:
        try:
            from repro import spans
        except ImportError:
            return None
        records = spans.recent()
    roots = [s for s in records if s.name == "run_rounds" and s.parent is None]
    calls = roots[-ctx["calls"]:] if ctx["calls"] > 0 else []
    slots = sum(s.counts.get("topk.pick_slots", 0) for s in calls)
    if not slots:
        return None
    return 100.0 * sum(s.counts.get("topk.picks", 0) for s in calls) / slots
