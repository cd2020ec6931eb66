"""The program's own spans and the per-layer metrics that read them.

The readers run on constructed traces (no chip needed); a tiny server
then serves a few steps under the profiler on the CPU, and its trace is
read back the way the harness reads a chip's."""
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

import harness
import xtrace

HERE = Path(__file__).resolve().parent
MS = 1_000_000  # ns
READERS = ("engine_host_ms", "queue_wait_ms", "control_plane_ms",
           "padded_item_share")
HARNESS_NAMES = ("server.step", "server.submit", "engine.step")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _sp(name, t0, t1, **stats):
    return (name, t0 * MS, t1 * MS, stats)


# spans before the first traced server step and after the last one, which
# every reader must leave out
OUTSIDE = [
    _sp("engine.step", 0, 6, eng=0, n=1),
    _sp("engine.plan", 0, 5, eng=0, admitted=5, wait_us=1_000_000,
        chunk_tokens=64),
    _sp("engine.launch", 5, 6, eng=0, kind="mixed", horizon=1, items=100,
        real_items=0),
    _sp("plane.tick", 31, 35, handovers=0),
]
HARNESS = [
    _sp("server.step", 10, 20, step_num=0),
    _sp("engine.step", 10, 19, eng=0, n=2),
    _sp("server.step", 20, 30, step_num=1),
    _sp("engine.step", 20, 28, eng=0, n=3),
]
PROGRAM = [
    # a mixed step: 4 ms of host work, 5 ms in the d2h
    _sp("engine.plan", 10, 11, eng=0, admitted=2, wait_us=3000,
        chunk_tokens=32),
    _sp("engine.tables", 11, 11.5, eng=0, rows=1),
    _sp("engine.stage", 11.5, 12, eng=0),
    _sp("engine.launch", 12, 13, eng=0, kind="mixed", horizon=1, items=16,
        real_items=12),
    _sp("engine.d2h", 13, 18, eng=0),
    _sp("engine.commit", 18, 18.5, eng=0, finished=0),
    _sp("engine.flush", 18.5, 19, eng=0),
    _sp("plane.tick", 19, 20, handovers=1),
    _sp("plane.migrate", 19.2, 19.8, req=7, src=0, dst=1),
    # a decode step: 3.5 ms of host work
    _sp("engine.plan", 20, 21, eng=0, admitted=0, wait_us=0,
        chunk_tokens=0),
    _sp("engine.tables", 21, 21.5, eng=0, rows=0),
    _sp("engine.launch", 21.5, 22.5, eng=0, kind="burst", horizon=1,
        items=8, real_items=8),
    _sp("engine.d2h", 22.5, 27, eng=0),
    _sp("engine.commit", 27, 27.5, eng=0, finished=1),
    _sp("engine.flush", 27.5, 28, eng=0),
    _sp("plane.tick", 28, 30, handovers=0),
]
EXPECT = {
    "engine_host_ms": (4.0 + 3.5) / 2,
    "queue_wait_ms": 3000 / 2 / 1e3,
    "control_plane_ms": (1.0 + 2.0) / 2,
    "padded_item_share": 100.0 * (1 - 20 / 24),
}


def _trace(spans):
    return xtrace.Trace([("fusion.1", 0, 1, 0)], spans, 1)


@pytest.mark.parametrize("name", READERS)
def test_reader_known_spans(name):
    got = _reader(name)({"trace": _trace(OUTSIDE + HARNESS + PROGRAM)})
    assert got == pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_without_trace_is_none(name):
    assert _reader(name)({"trace": None}) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_of_a_program_without_spans_is_none(name):
    # the harness's spans alone, as a program without its own would give
    assert _reader(name)({"trace": _trace(HARNESS)}) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_ignores_spans_outside_the_window(name):
    assert _reader(name)({"trace": _trace(OUTSIDE + HARNESS)}) is None


# ---------------------------------------------------------------------------
# a tiny server served under the profiler on the CPU
# ---------------------------------------------------------------------------
TINY = {"name": "tiny", "model_type": "qwen2", "hidden_size": 64,
        "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
        "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "serve": {"dtype": "bfloat16"}}
# each span the program emits, with the stats it carries and the span
# that must enclose it
SPANS = {
    "engine.plan": ({"eng", "admitted", "wait_us", "chunk_tokens"},
                    "engine.step"),
    "engine.tables": ({"eng", "rows"}, "engine.step"),
    "engine.stage": ({"eng"}, "engine.step"),
    "engine.launch": ({"eng", "kind", "horizon", "items", "real_items"},
                      "engine.step"),
    "engine.d2h": ({"eng"}, "engine.step"),
    "engine.commit": ({"eng", "finished"}, "engine.step"),
    "engine.flush": ({"eng"}, "engine.step"),
    "server.route": ({"req"}, "server.submit"),
    "server.stream": (set(), "server.step"),
    "plane.tick": ({"handovers"}, "server.step"),
    "plane.migrate": ({"req", "src", "dst"}, "plane.tick"),
}
# spans one engine step emits: always plan, d2h, commit and flush; tables
# and launch when it launches; stage on a mixed step
PER_ENGINE_STEP = 7


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two engines behind ``cascade`` with the fused mixed kernel
    (interpreted): prompts chunk beside decode rows and grow past the
    stage boundary, so every span fires. Traced from the first submit."""
    from repro.core.partition import PipelinePlan, Stage
    from repro.core.qoe import QoEModel
    from repro.models import build_model
    from repro.serving.request import ServeRequest
    from repro.serving.server import MILSServer, ServerConfig

    arch = harness.load_architecture("dense_gqa")
    model = build_model(arch.program_config("tiny", arch.dims(TINY), 64))
    params = model.init(jax.random.PRNGKey(0))
    plan = PipelinePlan([Stage(0.0, 24.0, 1),
                         Stage(24.0, float("inf"), 1)], 0.0)
    srv = MILSServer(model, params, plan,
                     QoEModel(np.array([1e-3, 1e-4, 1e-6, 0.0, 1e-6])),
                     ServerConfig(policy="cascade", seed=0,
                                  attn_backend="fused"),
                     max_slots=4, max_seq=64, prefill_token_budget=16)
    rec = harness.Recorder()
    rec.attach(srv)
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(i, rng.integers(0, 256, p).astype(np.int32), n)
            for i, (p, n) in enumerate([(14, 14), (9, 4), (20, 6),
                                        (6, 12), (21, 3)])]
    trace_dir = tmp_path_factory.mktemp("trace")
    # the python tracer's frames are not read, and slow the serve
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        for r in reqs[:4]:
            srv.submit(r)
        for step in range(40):
            if step == 2:
                srv.submit(reqs[4])
            srv.step()
            if len(srv.finished) == len(reqs):
                break
    tr = xtrace.load(xtrace.find_xplane(str(trace_dir)))
    return srv, rec, tr, len(reqs)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("name", sorted(SPANS))
def test_program_span_nested_with_stats(served, name):
    _, _, tr, _ = served
    stats, parent = SPANS[name]
    mine = [s for s in tr.spans if s[0] == name]
    outer = [s for s in tr.spans if s[0] == parent]
    assert mine, f"no {name} span in the trace"
    for s in mine:
        assert stats <= set(s[3]), (name, s[3])
        enclosing = [o for o in outer if _inside(s, o)]
        assert enclosing, f"{name} outside every {parent}"
        if "eng" in stats:
            assert enclosing[0][3]["eng"] == s[3]["eng"]


def test_no_program_span_uses_a_harness_name(served):
    srv, rec, tr, n = served
    counts = {h: sum(1 for s in tr.spans if s[0] == h)
              for h in HARNESS_NAMES}
    # the harness's own spans, and no more of each name
    assert counts["server.step"] == srv.steps
    assert counts["engine.step"] == len(rec.engine_steps)
    assert counts["server.submit"] == n
    assert all("n" in s[3] for s in tr.spans if s[0] == "engine.step")


def test_spans_per_step_are_bounded(served):
    srv, _, tr, _ = served
    ours = [s for s in tr.spans if s[0] in SPANS]
    for st in (s for s in tr.spans if s[0] == "engine.step"):
        inside = [s for s in ours if _inside(s, st)]
        assert 4 <= len(inside) <= PER_ENGINE_STEP, inside
    # a server step: one stream span per engine, the tick, and at most
    # the tick's migration budget of migrations
    cap = (len(srv.engines) + 1 + srv.cfg.max_migrations_per_step
           + PER_ENGINE_STEP * len(srv.engines))
    for st in (s for s in tr.spans if s[0] == "server.step"):
        assert len([s for s in ours if _inside(s, st)]) <= cap


def test_counters_agree_with_span_stats(served):
    srv, _, tr, n = served
    engines = srv.engines

    def total(name, key):
        return sum(int(s[3][key]) for s in tr.spans if s[0] == name)

    admitted = sum(e.admitted_total for e in engines)
    assert admitted == total("engine.plan", "admitted") == n
    wait_us = sum(e.queue_wait_s_total for e in engines) * 1e6
    plans = sum(1 for s in tr.spans if s[0] == "engine.plan")
    assert abs(total("engine.plan", "wait_us") - wait_us) <= plans
    assert sum(e.work_items for e in engines) \
        == total("engine.launch", "items")
    assert sum(e.real_work_items for e in engines) \
        == total("engine.launch", "real_items")
    assert srv.migrations == total("plane.tick", "handovers") > 0


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_a_served_trace(served, name):
    _, _, tr, _ = served
    got = _reader(name)({"trace": tr})
    assert got is not None and got >= 0
    if name == "padded_item_share":
        assert got < 100
