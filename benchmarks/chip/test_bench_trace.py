"""The trace reduction on a constructed trace (no chip needed)."""
import pytest

import xtrace

MS = 1_000_000  # ns


def _trace():
    # one chip: two ops back to back, a nested op, an idle gap under
    # engine.step and one under server.step only
    ops = [("fusion.1", 0 * MS, 2 * MS, 0),
           ("paged_mixed_attention", 2 * MS, 3 * MS, 0),
           ("while.1", 5 * MS, 9 * MS, 0),
           ("paged_mixed_attention", 6 * MS, 7 * MS, 0)]
    spans = [("server.step", 0, 10 * MS, {"step_num": 0}),
             ("engine.step", 0, 4.5 * MS, {"eng": 0, "n": 1}),
             ("engine.step", 4.6 * MS, 9.5 * MS, {"eng": 1, "n": 2})]
    return xtrace.Trace(ops, spans, 1)


def test_busy_idle_and_self_time():
    red = xtrace.reduce(_trace(), (0, 10 * MS))
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.007)          # 0-3 and 5-9 ms
    ops = dict((n, t) for n, t in red["device_ops"])
    assert ops["while.1"] == pytest.approx(0.003)         # 4 ms minus 1 nested
    assert ops["paged_mixed_attention"] == pytest.approx(0.002)
    gaps = dict((n, t) for n, t in red["idle_gaps"])
    # the 3-5 ms gap (midpoint 4) is inside engine.step n=1; 9-10 ms only in
    # server.step
    assert gaps["engine.step"] == pytest.approx(0.002)    # 3-5 ms
    assert gaps["server.step"] == pytest.approx(0.001)


def test_window_clips_ops():
    red = xtrace.reduce(_trace(), (1 * MS, 6.5 * MS))
    assert red["busy_s"] == pytest.approx(0.0035)         # 1-3, 5-6.5 ms


def test_kernel_time_per_span():
    per = xtrace.kernel_time_in_spans(_trace(), "engine.step",
                                      "paged_mixed_attention")
    assert [(s["n"], round(t, 6)) for s, t in per] == [(1, 0.001),
                                                       (2, 0.001)]


def test_two_chips_average():
    tr = xtrace.Trace([("a", 0, 4 * MS, 0), ("a", 0, 2 * MS, 1)],
                      [("server.step", 0, 4 * MS, {})], 2)
    red = xtrace.reduce(tr, (0, 4 * MS))
    assert red["busy_s"] == pytest.approx(0.003)


def test_seams_are_not_labelled_gaps():
    tr = xtrace.Trace([("a", 0, 1 * MS, 0), ("b", 1 * MS + 1000, 2 * MS, 0)],
                      [("server.step", 0, 2 * MS, {})], 1)
    gaps = dict(xtrace.reduce(tr, (0, 2 * MS))["idle_gaps"])
    assert set(gaps) == {"(seams between ops)"}
