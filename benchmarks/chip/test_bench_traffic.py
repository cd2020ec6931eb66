"""The traffic generator: a seed reproduces its backlog, every seed
serves the same sizes, and lengths stay in the mix's ranges."""
import json
from pathlib import Path

import numpy as np
import pytest

import traffic

MIXES = sorted((Path(__file__).parent / "traffic").glob("*.json"))


def _mix(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", MIXES, ids=[p.stem for p in MIXES])
def test_seed_reproduces_and_ranges(path):
    mix = _mix(path)
    a = traffic.build(mix, 2**31 + 77, 1000)
    b = traffic.build(mix, 2**31 + 77, 1000)
    assert len(a) == len(b) == mix["requests"]
    for x, y in zip(a, b):
        assert x.idx == y.idx and x.output_len == y.output_len
        assert np.array_equal(x.prompt, y.prompt)
    lo = min(c["prompt"]["min"] for c in mix["lengths"])
    hi = max(c["prompt"]["max"] for c in mix["lengths"])
    for r in a:
        assert lo <= len(r.prompt) <= hi
        assert 1 <= r.output_len
        assert len(r.prompt) + r.output_len <= mix["max_total"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 1000


@pytest.mark.parametrize("path", MIXES, ids=[p.stem for p in MIXES])
def test_every_seed_serves_the_same_sizes(path):
    mix = _mix(path)
    a = traffic.build(mix, 1, 1000)
    b = traffic.build(mix, 2, 1000)
    # the same sizes in the same order
    plan = lambda s: [(r.idx, len(r.prompt), r.output_len) for r in s]
    assert plan(a) == plan(b)
    # only the token ids differ
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_components_keep_their_shares():
    mix = _mix(Path(__file__).parent / "traffic" / "longctx-closed-loop.json")
    reqs = traffic.build(dict(mix, requests=500), 3, 1000)
    long = sum(1 for r in reqs if len(r.prompt) >= 8192)
    assert long == 100                    # 20% of 500, exactly
