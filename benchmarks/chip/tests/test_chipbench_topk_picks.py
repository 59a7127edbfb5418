"""The ``topk_pick_share.select`` reader on hand-built records of program
spans: the window's ``run_rounds`` calls are the newest ones, their pick
counts are summed before dividing, and it reads None, not a number, where
no such call counted a slot."""
import pytest

from chipbench import manifest
from repro.spans import Span

READ = manifest.metric_reader("topk_pick_share.select")


def _rounds(sid, picks=None, slots=None, name="run_rounds", parent=None):
    counts = {"xla.programs": 1}
    if slots is not None:
        counts.update({"topk.picks": picks, "topk.pick_slots": slots})
    return Span(name, sid, parent, sid if parent is None else parent,
                sid * 10, sid * 10 + 5, counts)


SETUP = [_rounds(1, 40_000, 81_200), _rounds(2, 9_000, 81_200)]
WINDOW = [_rounds(3, 120, 81_200), _rounds(4, 200, 81_200),
          _rounds(5, picks=7, slots=9, name="bench.other"),
          _rounds(6, 7, 9, parent=3)]


def test_share_sums_the_window_calls_before_dividing():
    assert READ({"calls": 2}, SETUP + WINDOW) == pytest.approx(
        100.0 * 320 / 162_400)


def test_the_window_is_the_newest_calls():
    assert READ({"calls": 1}, SETUP + WINDOW) == pytest.approx(
        100.0 * 200 / 81_200)


@pytest.mark.parametrize("records", [
    pytest.param([], id="no-spans"),
    pytest.param([_rounds(1), _rounds(2)], id="program-without-counts"),
    pytest.param([_rounds(1, 0, 0), _rounds(2, 0, 0)], id="lax-top-k"),
    pytest.param(WINDOW[2:], id="no-run_rounds-root"),
])
def test_none_where_no_call_counted_a_slot(records):
    assert READ({"calls": 2}, records) is None


def test_reads_the_programs_record_by_default(monkeypatch):
    from repro import spans

    monkeypatch.setattr(spans, "recent", lambda: SETUP + WINDOW)
    assert READ({"calls": 2}) == pytest.approx(100.0 * 320 / 162_400)
