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


def _collective_share():
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "metric_collective_share",
        Path(__file__).parent / "metrics" / "collective_share.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_collective_share_two_chips():
    read = _collective_share().read
    # chip 0: busy 0-8 ms, of it an all-reduce 2-3 ms nested in a fusion
    # and an async start/done pair 5-6 ms: 2 of 8 ms. Chip 1: busy 0-4 ms,
    # an all-gather 1-2 ms: 1 of 4 ms. An op outside the traced server
    # steps, and one that only consumes an all-reduce's result, count for
    # nothing.
    ops = [("fusion.1", 0, 4 * MS, 0),
           ("all-reduce.7", 2 * MS, 3 * MS, 0),
           ("fusion.2 = bf16[8,5120] fusion(all-reduce.7)", 4 * MS, 5 * MS,
            0),
           ("all-reduce-start.3", 5 * MS, 5.5 * MS, 0),
           ("all-reduce-done.3", 5.5 * MS, 6 * MS, 0),
           ("fusion.3", 6 * MS, 8 * MS, 0),
           ("all-reduce.9", 11 * MS, 12 * MS, 0),
           ("fusion.1", 0, 4 * MS, 1),
           ("all-gather.2", 1 * MS, 2 * MS, 1)]
    spans = [("server.step", 0, 5 * MS, {}), ("server.step", 5 * MS, 10 * MS, {})]
    got = read({"trace": xtrace.Trace(ops, spans, 2)})
    assert got == pytest.approx(100.0 * (2 / 8 + 1 / 4) / 2)


def test_collective_share_reads_nothing_without_collectives():
    read = _collective_share().read
    one_chip = xtrace.Trace([("paged_mixed_attention.3", 0, 2 * MS, 0)],
                            [("server.step", 0, 3 * MS, {})], 1)
    assert read({"trace": one_chip}) is None
    assert read({"trace": None}) is None


@pytest.mark.parametrize("name,want", [
    ("all-reduce.12", True), ("all-reduce-start.1", True),
    ("all-gather-done.4", True), ("reduce-scatter.2", True),
    ("collective-permute-start", True), ("%all-to-all.1", True),
    ("fusion.2 = bf16[1,5120] fusion(all-reduce.1)", False),
    ("paged_mixed_attention.11", False), ("reduce.5", False)])
def test_collective_names(name, want):
    assert _collective_share().is_collective(name) is want
