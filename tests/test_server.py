"""Multi-engine CascadeInfer server over real model state."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.partition import PipelinePlan, Stage
from repro.core.qoe import QoEModel
from repro.models import build_model
from repro.serving.request import ServeRequest
from repro.serving.server import MILSServer, ServerConfig


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _plan(E, boundary=48.0):
    lo = E // 2
    return PipelinePlan([Stage(0.0, boundary, E - lo),
                         Stage(boundary, float("inf"), lo)], 0.0)


def _qoe():
    return QoEModel(np.array([1e-3, 1e-4, 1e-6, 0.0, 1e-6]))


def _reqs(rng, cfg, n, plen=20, new=(8, 50)):
    return [ServeRequest(i, rng.integers(0, cfg.vocab_size, plen)
                         .astype(np.int32), int(rng.integers(*new)))
            for i in range(n)]


def test_cascade_server_completes_and_migrates(setup, rng):
    cfg, model, params = setup
    srv = MILSServer(model, params, _plan(4), _qoe(),
                     ServerConfig(policy="cascade", seed=0),
                     max_slots=3, max_seq=96)
    reqs = _reqs(rng, cfg, 8)
    fin = srv.run(reqs, max_steps=400)
    assert len(fin) == 8
    assert srv.migrations > 0, "long requests must cross the stage boundary"


def test_migrated_decode_identical_to_single_engine(setup, rng):
    cfg, model, params = setup
    srv = MILSServer(model, params, _plan(4), _qoe(),
                     ServerConfig(policy="cascade", seed=0),
                     max_slots=3, max_seq=96)
    reqs = _reqs(rng, cfg, 6, new=(30, 60))
    fin = srv.run(reqs, max_steps=400)
    for r in fin[:3]:
        single = MILSServer(model, params,
                            PipelinePlan([Stage(0.0, float("inf"), 1)], 0.0),
                            _qoe(), ServerConfig(policy="round-robin"),
                            max_slots=3, max_seq=96)
        ref = ServeRequest(100 + r.req_id, r.prompt.copy(),
                           r.max_new_tokens)
        single.run([ref], max_steps=400)
        assert r.generated == ref.generated, \
            f"req {r.req_id}: migration changed greedy decode"


def test_round_robin_and_least_loaded_policies(setup, rng):
    cfg, model, params = setup
    for policy in ("round-robin", "least-loaded"):
        srv = MILSServer(model, params, _plan(2), _qoe(),
                         ServerConfig(policy=policy), max_slots=3,
                         max_seq=96)
        fin = srv.run(_reqs(rng, cfg, 4), max_steps=300)
        assert len(fin) == 4


def test_open_loop_arrivals_stream_and_tail_metrics(setup, rng):
    cfg, model, params = setup
    tokens = []
    srv = MILSServer(model, params, _plan(4), _qoe(),
                     ServerConfig(policy="cascade", seed=0),
                     max_slots=3, max_seq=96,
                     on_token=lambda r, t: tokens.append((r.req_id, t)))
    reqs = _reqs(rng, cfg, 6)
    for i, r in enumerate(reqs):
        srv.submit_at(r, step=3 * i)
    fin = srv.run(max_steps=400)
    assert len(fin) == 6
    # arrival schedule honored: nothing starts before its arrival step
    for r in fin:
        assert r.arrival_step >= 0 and r.first_token_step > r.arrival_step
    # every generated token streamed exactly once
    assert len(tokens) == sum(len(r.generated) for r in fin)
    s = srv.summary()
    for key in ("ttft_steps_p50", "ttft_steps_p95", "ttft_steps_p99",
                "e2e_steps_p50", "e2e_steps_p95", "e2e_steps_p99"):
        assert key in s and s[key] >= 0
    assert s["ttft_steps_p50"] <= s["ttft_steps_p99"]
    # per-stage-pair migration counts sum to the total
    assert sum(v for k, v in s.items()
               if k.startswith("migrations_s")) == s["migrations"]


def test_summary_wall_clock_latency(setup, rng):
    """Queue wait and TTFT in seconds on the host clock, from submission:
    stamped whether or not a token callback is set."""
    cfg, model, params = setup
    srv = MILSServer(model, params, _plan(2), _qoe(),
                     ServerConfig(policy="cascade", seed=0),
                     max_slots=3, max_seq=96)
    fin = srv.run(_reqs(rng, cfg, 6, new=(4, 12)), max_steps=300)
    assert len(fin) == 6
    s = srv.summary()
    for name in ("queue_wait_s", "ttft_s"):
        assert 0 <= s[f"{name}_p50"] <= s[f"{name}_p95"]
    assert s["queue_wait_s_p50"] <= s["ttft_s_p50"]
    assert s["queue_wait_s_p95"] <= s["ttft_s_p95"]
    assert all(r.t_submit <= r.t_admit <= r.t_first_token for r in fin)
    # the engines' counters hold the same waits
    assert sum(e.admitted_total for e in srv.engines) == 6
    assert sum(e.queue_wait_s_total for e in srv.engines) == pytest.approx(
        sum(r.t_admit - r.t_submit for r in fin))


@pytest.mark.parametrize("refinement,balancing",
                         [("quantity", "full"), ("memory", "inter-stage"),
                          ("none", "rr")])
def test_server_runs_ablation_knobs(setup, rng, refinement, balancing):
    """Fig. 15/16 ablations on the real-engine path (previously sim-only)."""
    cfg, model, params = setup
    srv = MILSServer(model, params, _plan(4), _qoe(),
                     ServerConfig(policy="cascade", refinement=refinement,
                                  balancing=balancing, refine_every=4),
                     max_slots=3, max_seq=96)
    fin = srv.run(_reqs(rng, cfg, 6), max_steps=400)
    assert len(fin) == 6
    bounds = srv.stage_bounds
    assert bounds[0][0] == 0.0 and bounds[-1][1] == float("inf")
    if refinement == "none":
        assert bounds[0][1] == 48.0, "refinement=none must freeze boundaries"


def test_boundaries_stay_monotone_under_refinement(setup, rng):
    cfg, model, params = setup
    srv = MILSServer(model, params, _plan(4), _qoe(),
                     ServerConfig(policy="cascade", refine_every=4, seed=1),
                     max_slots=3, max_seq=96)
    srv.run(_reqs(rng, cfg, 10), max_steps=400)
    bounds = srv.stage_bounds
    assert bounds[0][0] == 0.0
    assert bounds[-1][1] == float("inf")
    for (lo, hi), (lo2, hi2) in zip(bounds, bounds[1:]):
        assert hi == lo2 and lo < hi
