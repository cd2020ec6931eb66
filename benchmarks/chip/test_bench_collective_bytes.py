"""The reader ``collective_bytes_per_token`` on constructed spans: the
``collective_bytes`` stats of the traced window's ``engine.launch`` spans
over the tokens stamped inside the window's server steps, and nothing
where there is no trace, no such stat, a one-chip program's zeros, or
only launches outside the window. The spans are those of
``test_bench_spans``."""
import pytest

from test_bench_spans import HARNESS, MS, OUTSIDE, PROGRAM, _reader, _trace


class _Rec:
    """The harness's host-clock record: server steps (s) and stamps."""

    def __init__(self, server_steps, stamps):
        self.server_steps, self.stamps = server_steps, stamps


def _with_collectives(spans, per_launch):
    return [(n, s, e, dict(st, collective_bytes=per_launch[n, s / MS]))
            if (n, s / MS) in per_launch else (n, s, e, st)
            for n, s, e, st in spans]


# the window's two launches at 12 and 21.5 ms, and one outside it at 5 ms
COLLECTIVES = {("engine.launch", 12): 9_000, ("engine.launch", 21.5): 3_000,
               ("engine.launch", 5): 70_000}
# server steps 0 and 1 on the host clock; three tokens inside them, one
# before the first
REC = _Rec([(1.0, 2.0), (2.0, 3.0)], {4: [1.5, 2.5], 5: [2.99], 6: [0.5]})


def test_collective_bytes_per_token_known_spans():
    read = _reader("collective_bytes_per_token")
    spans = _with_collectives(OUTSIDE + HARNESS + PROGRAM, COLLECTIVES)
    got = read({"trace": _trace(spans), "rec": REC})
    assert got == pytest.approx((9_000 + 3_000) / 3)


@pytest.mark.parametrize("case", ["no-trace", "one-chip", "no-stat",
                                  "outside-only"])
def test_collective_bytes_per_token_reads_nothing(case):
    read = _reader("collective_bytes_per_token")
    spans = OUTSIDE + HARNESS + PROGRAM
    if case == "one-chip":
        spans = _with_collectives(spans, {k: 0 for k in COLLECTIVES})
    elif case == "outside-only":
        spans = _with_collectives(OUTSIDE + HARNESS, COLLECTIVES)
    trace = None if case == "no-trace" else _trace(spans)
    assert read({"trace": trace, "rec": REC}) is None
