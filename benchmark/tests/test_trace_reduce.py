"""trace_reduce against a small trace recorded on the v5e in PR 22
(testdata/small.xplane.pb, made by record_trace.py): three runs of one
jitted program inside a `bench_window` annotation. The expected numbers
were worked out by hand from the events record_trace.py printed (ns):

- bench_window on the host: 45540028 to 111084564.
- jit__lambda on XLA Modules: 44630546-44643926, 66386777-66400134,
  88274317-88287709 (13380 + 13357 + 13392 = 40129). The first starts
  before the window on the host's clock: the clock tolerance keeps it.
- XLA Ops per run: copy-start, copy-done, fusion. Their union is
  14 + 2 + 13358, 13 + 2 + 13335, and 13 + 13374 (the third copy-done
  ends where its fusion starts) = 40111 busy.
- window = 65544536 + 2 * 2000000 (tolerance) = 69544536.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

TRACE = BENCH / "testdata" / "small.xplane.pb"
NS = 1e-9


@pytest.fixture(scope="module")
def red():
    return trace_reduce.read(TRACE)


def test_busy_and_window(red):
    assert red["chips"] == 1
    assert red["window"] == (45540028.0, 111084564.0)
    assert red["busy_s"] == pytest.approx(40111 * NS, rel=1e-9)
    assert red["window_s"] == pytest.approx(69544536 * NS, rel=1e-9)


def test_modules_and_ops(red):
    assert red["modules"] == {"jit__lambda": pytest.approx(40129 * NS,
                                                           rel=1e-9)}
    assert red["ops"]["fusion"] == pytest.approx(40065 * NS, rel=1e-9)
    assert red["ops"]["copy-start"] == pytest.approx(40 * NS, rel=1e-9)
    assert red["ops"]["copy-done"] == pytest.approx(6 * NS, rel=1e-9)
    assert trace_reduce.top(red["ops"], 1)[0][0] == "fusion"


def test_idle_labelled_by_host_span(red):
    idle = sum(e - s for s, e in red["idle"])
    assert idle == pytest.approx(69544536 - 40111, rel=1e-9)
    lab = trace_reduce.label_idle(
        red["idle"], [("bench_window", 45540028.0, 111084564.0)])
    # the first run lies before the annotation on the host's clock: the
    # gap before it and its two 1 ns gaps between ops are "untraced"
    assert lab["untraced"] == pytest.approx(
        (44630549 - (45540028 - 2e6) + 2) * NS, rel=1e-9)
    assert lab["bench_window"] == pytest.approx(
        (69544536 - 40111) * NS - lab["untraced"], rel=1e-9)


def test_union_and_gaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [
        (0, 3), (5, 9)]
    assert trace_reduce.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4),
                                                          (5, 6)]
