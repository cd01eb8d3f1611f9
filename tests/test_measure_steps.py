"""The trace reduction of ``scripts/measure_steps.py``: device busy time is
the union of op intervals, so nested and overlapping ops count once, and a
step's idle share is read from the median step, so one stall does not
set it."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

from measure_steps import step_idle, union_ms  # noqa: E402


@pytest.mark.parametrize("intervals,busy_ns", [
    ([], 0),
    ([(0, 10)], 10),
    ([(0, 10), (20, 25)], 15),                # disjoint: a gap is idle
    ([(0, 100), (10, 20), (30, 40)], 100),    # nested: a while and its body
    ([(5, 15), (0, 10), (12, 30)], 30),       # overlapping, out of order
    ([(0, 10), (10, 20)], 20),                # touching
])
def test_union_ms(intervals, busy_ns):
    assert union_ms(intervals) == pytest.approx(busy_ns / 1e6)


def test_step_idle_median_ignores_one_stall():
    ms = 1_000_000
    starts = [0, 10, 20, 30, 1030, 1040]     # one 992 ms host stall
    got = step_idle([(s * ms, (s + 8) * ms) for s in starts])
    assert got["busy_ms"] == pytest.approx(8)
    assert got["period_ms"] == pytest.approx(10)
    assert got["idle"] == pytest.approx(0.2)
    assert got["max_gap_ms"] == pytest.approx(992)


def test_step_idle_one_step_has_no_period():
    got = step_idle([(0, 5_000_000)])
    assert got == {"busy_ms": pytest.approx(5), "max_gap_ms": 0.0}
