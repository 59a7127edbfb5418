"""The program's own spans and counts (``repro.spans``), put on the
trace's clock.

The profiler trace keeps only the harness's spans, so the program's are
read from its in-memory record: the last ``ctx["calls"]`` top-level
``run_fl`` spans are the window's calls (the plain references that run
after the window import nothing of the program). They are paired in
order with the ``bench.run_fl`` spans inside the traced window, and each
call's spans are shifted by the start of its ``bench.run_fl`` span.
Within a call every span comes from one clock, so what is left of error
is the host time between the two spans' starts, microseconds.

A pairing holds only where each ``run_fl`` span lasts as long as its
``bench.run_fl`` span and every call is shifted by the same amount, both
within ``SLACK_NS``: a call of the set-up, or one the record lost, would
differ by the seconds an experiment takes. Where the program records no
such spans (an older program), or they do not pair, every reader gives
None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from chipbench.trace import length, union, work_ops

ROOT = "run_fl"
HARNESS = "bench.run_fl"
SLACK_NS = 50_000_000

# (name, start, end, counts): a program span on the trace's clock
Aligned = Tuple[str, int, int, Dict[str, int]]


def _recorded():
    try:
        from repro import spans
    except ImportError:
        return None
    return list(spans.recent())


def window_calls(ctx, records=None) -> Optional[List[List[Aligned]]]:
    """Per window call, oldest first, its program spans on the trace's
    clock with its ``run_fl`` span first; None where the program's calls
    cannot be paired with the harness's."""
    records = _recorded() if records is None else list(records)
    calls, trace = ctx["calls"], ctx["trace"]
    if records is None or calls <= 0:
        return None
    roots = [s for s in records if s.name == ROOT and s.parent is None]
    lo, hi = trace.window
    harness = [s for s in trace.spans
               if s[0] == HARNESS and lo <= s[1] and s[2] <= hi]
    if len(roots) < calls or len(harness) != calls:
        return None
    pairs = list(zip(sorted(roots[-calls:], key=lambda s: s.start_ns),
                     sorted(harness, key=lambda s: s[1])))
    shifts = [h[1] - r.start_ns for r, h in pairs]
    if (max(shifts) - min(shifts) > SLACK_NS
            or any(abs((h[2] - h[1]) - (r.end_ns - r.start_ns)) > SLACK_NS
                   for r, h in pairs)):
        return None
    out = []
    for (root, _), shift in zip(pairs, shifts):
        members = sorted((s for s in records if s.root == root.id),
                         key=lambda s: (s is not root, s.start_ns))
        out.append([(s.name, s.start_ns + shift, s.end_ns + shift,
                     s.counts) for s in members])
    return out


def idle_share(ctx, name: str, records=None) -> Optional[float]:
    """Device idle time inside the window calls' ``name`` spans, as a
    percentage of the traced window, averaged over the chips."""
    calls = window_calls(ctx, records)
    trace = ctx["trace"]
    if calls is None or trace.window_s <= 0 or not trace.devices:
        return None
    lo, hi = trace.window
    inside = union([(a, b) for call in calls for n, a, b, _ in call
                    if n == name], lo, hi)
    if not inside:
        return None
    idle = 0
    for ops in trace.devices.values():
        busy = [(o.start, o.end) for o in work_ops(ops)]
        idle += sum((b - a) - length(union(busy, a, b)) for a, b in inside)
    return 100.0 * idle / len(trace.devices) / (hi - lo)


def call_counts(ctx, records=None) -> Optional[Dict[str, int]]:
    """The window calls' counts, summed; each call's ``run_fl`` span
    holds its whole call's."""
    calls = window_calls(ctx, records)
    if calls is None:
        return None
    total: Dict[str, int] = {}
    for call in calls:
        for k, v in call[0][3].items():
            total[k] = total.get(k, 0) + v
    return total
