"""The program's spans and counts put on the trace's clock, and the four
readers built on them, on a hand-built trace and a hand-built record of
program spans: each window call shifted onto its ``bench.run_fl`` span,
idle time inside the program's set-up and history spans, compilations
per call and the share of trained SGD slots aggregated; and None, not a
number, where the program's calls do not pair with the harness's."""
import sys

import pytest

from chipbench import manifest, program_spans as ps
from chipbench import trace as tr
from repro.spans import Span

CONV = ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, "
        "calls=%fused_computation.1")
WHILE = ("%while.9 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1)")
U = 1_000_000  # ns in the unit the timelines below are written in (ms)
READERS = ("setup_idle_share.train", "history_idle_share.train",
           "cache_loads_per_call.train", "sgd_useful_share.train")


def op(start, end, text=CONV):
    return tr.parse_op(start * U, (end - start) * U, text)


def _call(sid, t0, counts, setup=200, scan=250, history=30):
    """One ``run_fl`` call on the program's clock, starting at ``t0``:
    set-up, scan and history back to back (ms)."""
    t0 = t0 * U
    a, b, c = (t0 + setup * U, t0 + (setup + scan) * U,
               t0 + (setup + scan + history) * U)
    kids = [Span("fl.setup", sid + 1, sid, sid, t0, a),
            Span("fl.scan", sid + 2, sid, sid, a, b),
            Span("fl.history", sid + 3, sid, sid, b, c)]
    return kids + [Span("run_fl", sid, None, sid, t0, c, dict(counts))]


# the set-up call, then the window's two; the program's clock runs 7e6 ms
# ahead of the trace's
SHIFT = 7_000_000
SETUP_CALL = _call(1, SHIFT - 5_000, {"xla.programs": 90, "sgd.slots_trained":
                                      500, "sgd.slots_aggregated": 500},
                   setup=4_000)
CALL1 = _call(11, SHIFT + 10, {"xla.programs": 2, "xla.cache_loads": 2,
                               "sgd.slots_trained": 500,
                               "sgd.slots_aggregated": 480})
CALL2 = _call(21, SHIFT + 510, {"xla.programs": 3, "xla.cache_loads": 3,
                                "sgd.slots_trained": 500,
                                "sgd.slots_aggregated": 500})
RECORDS = SETUP_CALL + CALL1 + CALL2


def _ctx():
    """Window 0..1000 ms with two calls. Call 1 (10..490): set-up 10..210
    with the device busy 50..150, scan 210..460 busy throughout, history
    460..490 busy 470..480. Call 2 (510..990): set-up 510..710 busy
    600..650, scan busy, history 960..990 idle. A while loop spans the
    window and is not busy time."""
    dev = [op(0, 1000, WHILE), op(50, 150), op(210, 460), op(470, 480),
           op(600, 650), op(710, 960)]
    spans = [(n, a * U, b * U) for n, a, b in [
        ("bench.window", 0, 1000), ("bench.call", 0, 500),
        ("bench.run_fl", 10, 490), ("bench.call", 500, 1000),
        ("bench.run_fl", 510, 990)]]
    return {"trace": tr.from_parts({"/device:TPU:0": dev}, spans),
            "calls": 2}


def test_each_call_is_shifted_onto_its_harness_span():
    calls = ps.window_calls(_ctx(), RECORDS)
    assert [[(n, a // U, b // U) for n, a, b, _ in c] for c in calls] == [
        [("run_fl", 10, 490), ("fl.setup", 10, 210), ("fl.scan", 210, 460),
         ("fl.history", 460, 490)],
        [("run_fl", 510, 990), ("fl.setup", 510, 710), ("fl.scan", 710, 960),
         ("fl.history", 960, 990)]]


def test_idle_inside_program_spans():
    ctx = _ctx()
    # set-up idle: 200 - 100 in call 1, 200 - 50 in call 2, of 1000
    assert ps.idle_share(ctx, "fl.setup", RECORDS) == pytest.approx(25.0)
    # history idle: 30 - 10 and 30
    assert ps.idle_share(ctx, "fl.history", RECORDS) == pytest.approx(5.0)
    assert ps.idle_share(ctx, "fl.scan", RECORDS) == pytest.approx(0.0)
    assert ps.idle_share(ctx, "fl.nothing", RECORDS) is None


def test_idle_averages_over_chips():
    ctx = _ctx()
    ctx["trace"].devices["/device:TPU:1"] = [op(0, 1000)]
    assert ps.idle_share(ctx, "fl.setup", RECORDS) == pytest.approx(12.5)


def test_counts_sum_the_window_calls_only():
    assert ps.call_counts(_ctx(), RECORDS) == {
        "xla.programs": 5, "xla.cache_loads": 5,
        "sgd.slots_trained": 1000, "sgd.slots_aggregated": 980}


@pytest.fixture
def recorded(monkeypatch):
    """Readers take the record the program keeps; here a hand-built one."""
    held = {"records": RECORDS}
    monkeypatch.setattr(ps, "_recorded", lambda: list(held["records"]))
    return held


@pytest.mark.parametrize("name,want", [
    ("setup_idle_share.train", 25.0), ("history_idle_share.train", 5.0),
    ("cache_loads_per_call.train", 2.5), ("sgd_useful_share.train", 98.0)])
def test_readers(recorded, name, want):
    assert manifest.metric_reader(name)(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", [
    "no_program_spans", "lost_call", "extra_harness_call", "shifted_call"])
def test_unpaired_calls_read_none(recorded, name, case):
    ctx = _ctx()
    if case == "no_program_spans":
        recorded["records"] = []
    elif case == "lost_call":
        # the window's second call left no span: the set-up call would
        # pair with the first
        recorded["records"] = SETUP_CALL + CALL1
    elif case == "extra_harness_call":
        ctx["trace"].spans.append(("bench.run_fl", 995 * U, 999 * U))
        ctx["calls"] = 3
    else:
        # the second call's program span starts 0.1 s late for its
        # harness span: not the same call
        recorded["records"] = SETUP_CALL + CALL1 + _call(
            21, SHIFT + 610, {"sgd.slots_trained": 1})
    assert manifest.metric_reader(name)(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_recorder_reads_none(monkeypatch, name):
    import repro

    monkeypatch.delattr(repro, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert manifest.metric_reader(name)(_ctx()) is None


def test_the_real_recorder_feeds_the_readers():
    """Spans the program records pair with harness spans stamped from the
    same clock."""
    from repro import spans

    with spans.span("run_fl") as root:
        with spans.span("fl.setup"):
            spans.count("xla.programs", 4)
    ctx = {"trace": tr.from_parts(
        {"/device:TPU:0": [tr.parse_op(root.start_ns, 1, CONV)]},
        [("bench.window", root.start_ns - 5, root.end_ns + 5),
         ("bench.run_fl", root.start_ns - 1, root.end_ns + 1)]), "calls": 1}
    assert ps.call_counts(ctx) == {"xla.programs": 4}
    assert manifest.metric_reader("cache_loads_per_call.train")(ctx) == 4
