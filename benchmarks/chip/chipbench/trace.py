"""Profiler capture and the reduction from a device trace to numbers.

The JAX profiler writes an XSpace (``*.xplane.pb``). On a TPU each chip is
a plane ``/device:TPU:<i>`` whose ``XLA Ops`` line holds one event per
HLO operation run, named by the instruction's text
(``%fusion.12 = f32[...] fusion(...), kind=kOutput, calls=...``); the
host plane ``/host:CPU`` holds the harness's ``TraceAnnotation`` spans on
the Python thread, on the same clock. Control-flow operations (``while``,
``conditional``, ``call``) span the operations they run and are left out
of busy time and of every share.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


@dataclass
class Op:
    start: int          # ns, profiler clock
    end: int
    text: str           # the HLO instruction text the trace names it by
    opcode: str
    kind: str           # fusion kind (kLoop, kOutput, ...) or ""

    @property
    def name(self) -> str:
        return self.text.split(" = ", 1)[0].lstrip("%")


def parse_op(start: int, dur: int, text: str) -> Op:
    m = _OPCODE.search(text)
    k = _KIND.search(text)
    return Op(int(start), int(start + dur), text, m.group(1) if m else "",
              k.group(1) if k else "")


@dataclass
class Trace:
    devices: Dict[str, List[Op]]            # plane name -> ops, by start
    spans: List[Tuple[str, int, int]]       # harness spans on the host
    window: Tuple[int, int]                 # the traced window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


@contextlib.contextmanager
def capture(log_dir: str) -> Iterator[None]:
    """Device and host tracing, without the Python function tracer: its
    hundreds of thousands of events would slow the host the window
    measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Op]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = sorted(
                        (parse_op(e.start_ns, e.duration_ns, e.name)
                         for e in line.events), key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    return from_parts(devices, spans)


def from_parts(devices: Dict[str, List[Op]],
               spans: List[Tuple[str, int, int]]) -> Trace:
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if window:
        w = (window[0][1], window[0][2])
    else:
        ends = [o for ops in devices.values() for o in ops]
        w = (min(o.start for o in ends), max(o.end for o in ends))
    return Trace(devices, sorted(spans, key=lambda s: s[1]), w)


def union(intervals: List[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Merged, clipped intervals: the time at least one of them covers."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: List[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def work_ops(ops: List[Op]) -> List[Op]:
    return [o for o in ops if o.opcode not in CONTAINERS]


def busy_ns(trace: Trace, match: Optional[Callable[[Op], bool]] = None
            ) -> Dict[str, int]:
    """Per device: the time inside the window in which at least one
    operation (that ``match`` accepts) ran."""
    lo, hi = trace.window
    return {dev: length(union([(o.start, o.end) for o in work_ops(ops)
                               if match is None or match(o)], lo, hi))
            for dev, ops in trace.devices.items()}


def busy_s(trace: Trace) -> float:
    """Device busy seconds, averaged over the chips in the trace."""
    per = busy_ns(trace)
    return sum(per.values()) / max(len(per), 1) / 1e9


def share(trace: Trace, match: Callable[[Op], bool]) -> Optional[float]:
    """Share of device busy time in operations ``match`` accepts, over all
    chips; None where the trace holds no busy time or no such operation."""
    total = sum(busy_ns(trace).values())
    part = sum(busy_ns(trace, match).values())
    if total == 0 or part == 0:
        return None
    return part / total


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time (summed by name over the
    chips), and the device's idle time inside the window summed by the
    innermost harness span the host was in at the middle of each gap."""
    lo, hi = trace.window
    by_name: Dict[str, int] = {}
    idle: Dict[str, int] = {}
    host = sorted((s for s in trace.spans if s[0] != WINDOW_SPAN),
                  key=lambda s: s[1])
    starts = [s[1] for s in host]
    for ops in trace.devices.values():
        for o in work_ops(ops):
            a, b = max(o.start, lo), min(o.end, hi)
            if b > a:
                by_name[o.name] = by_name.get(o.name, 0) + (b - a)
        busy = union([(o.start, o.end) for o in work_ops(ops)], lo, hi)
        prev = lo
        for a, b in busy + [(hi, hi)]:
            if a > prev:
                label = _innermost(host, starts, (prev + a) // 2)
                idle[label] = idle.get(label, 0) + (a - prev)
            prev = max(prev, b)
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v / 1e9] for n, v in top_ops],
            "idle_gaps": [[n, v / 1e9] for n, v in top_idle]}


def _innermost(host: List[Tuple[str, int, int]], starts: List[int],
               t: int) -> str:
    """The shortest span covering ``t``. Harness spans nest a few deep,
    so only the last few that start before ``t`` can cover it."""
    i = bisect.bisect_right(starts, t)
    inside = [s for s in host[max(0, i - 8):i] if t < s[2]]
    return (min(inside, key=lambda s: s[2] - s[1])[0] if inside
            else "outside any harness span")


# ------------------------------------------------ operation classes
# Shared by the metric readers, so that two metrics never disagree on
# what an operation is.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


def is_convolution(o: Op) -> bool:
    """A convolution, or a fusion that holds one: on the TPU those are the
    output fusions (``kind=kOutput``), rooted at a convolution or a dot.
    In the ResNet cells the only dots are the classifier head and the
    aggregation's weighted sum."""
    return (o.opcode == "convolution"
            or (o.opcode == "fusion" and o.kind == "kOutput")
            or (o.opcode == "custom-call" and "conv" in o.text.lower()))


def is_topk(o: Op) -> bool:
    """What computes a top-k: a sort (XLA's top-k on the TPU), XLA's TopK
    custom call, or a Pallas kernel (``tpu_custom_call``; the selection
    step's only one is the top-k kernel)."""
    t = o.text
    return (o.opcode == "sort"
            or (o.opcode == "custom-call"
                and ('"TopK"' in t or "tpu_custom_call" in t
                     or "topk" in o.name.lower())))


def is_collective(o: Op) -> bool:
    return (o.opcode.startswith(COLLECTIVES)
            or (o.opcode == "fusion" and any(
                c in o.name for c in COLLECTIVES)))
