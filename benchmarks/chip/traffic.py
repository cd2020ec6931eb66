"""Traffic: one general generator, driven by a mix file (``traffic/<mix>.json``).

A mix is a backlog of requests, in order, that ``concurrency`` closed-loop
clients send: each client sends the next request once its last one has
finished (``harness.Clients``). Requests go out between server steps, as
the finishes fall, never by the clock, so the server's step sequence
depends on the request sizes alone, not on the host's timing. The length
samplers are copied from the program's simulator workload module
(log-normal and log-uniform lengths) so that a change to the program
cannot move the yardstick.

Every seed serves the same work in the same order: the request sizes are
drawn from the mix's own ``pool_seed``, and the run's ``--seed`` draws
only the token ids. The server then meets the same step shapes on every
seed, and the warm-up, which serves the same sizes, meets them first.

A mix file holds:

* ``requests``: the backlog's length; ``concurrency``: the clients;
* ``lengths``: components ``{"weight", "prompt": dist, "output": dist}``,
  a ``dist`` being ``{"kind": "lognormal", "median", "sigma", "min",
  "max"}`` or ``{"kind": "loguniform", "min", "max"}``;
* ``max_total``: cap on prompt + output tokens;
* ``pool_seed``; ``lead_in_steps`` (server steps before the window
  opens); ``warmup_seed`` (the token ids of the warm-up, see
  ``harness._warm_up``); ``sample_requests`` (requests the correctness
  check compares).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    idx: int
    prompt: np.ndarray         # int32 token ids
    output_len: int


# ---------------------------------------------------------------------------
# samplers (copied from the simulator's workload module)
# ---------------------------------------------------------------------------
def _sample(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = float(dist["min"]), float(dist["max"])
    if dist["kind"] == "lognormal":
        x = rng.lognormal(math.log(float(dist["median"])),
                          float(dist["sigma"]), n)
    elif dist["kind"] == "loguniform":
        x = np.exp(rng.uniform(math.log(lo), math.log(hi), n))
    else:
        raise ValueError(f"unknown length distribution {dist['kind']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------
def sizes(mix: dict):
    """(prompt lengths, output lengths) of the backlog, in serving order,
    from the mix's ``pool_seed`` alone."""
    rng = np.random.default_rng(mix["pool_seed"])
    n = int(mix["requests"])
    comps = mix["lengths"]
    w = np.array([float(c["weight"]) for c in comps])
    # component counts are fixed shares of n, not draws: every backlog of
    # a given depth holds the same number of requests of each kind
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    ins, outs = [], []
    for c, k in zip(comps, counts):
        ins.append(_sample(c["prompt"], int(k), rng))
        outs.append(_sample(c["output"], int(k), rng))
    ins, outs = np.concatenate(ins), np.concatenate(outs)
    cap = int(mix["max_total"])
    ins = np.minimum(ins, cap - 1)
    outs = np.maximum(np.minimum(outs, cap - ins), 1)
    # the backlog is drawn component by component; one fixed shuffle
    # mixes the components over it
    order = rng.permutation(n)
    return ins[order], outs[order]


def build(mix: dict, seed: int, vocab: int) -> List[Req]:
    """The backlog of one run: the mix's sizes, token ids from ``seed``."""
    ins, outs = sizes(mix)
    rng = np.random.default_rng(seed)
    return [Req(i, rng.integers(0, vocab, int(p)).astype(np.int32), int(o))
            for i, (p, o) in enumerate(zip(ins, outs))]
