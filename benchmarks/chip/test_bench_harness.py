"""The harness driven end to end on the CPU at a tiny size: a sound run
is correct, and a run with the timed path broken underneath is not.
Also: the command refuses to run without a TPU, and in a directory that
holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import harness

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

TINY = {"name": "tiny", "architecture": "dense_gqa", "model_type": "qwen2",
        "hidden_size": 64,
        "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
        "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "serve": {"dtype": "bfloat16", "policy": "cascade", "tp": 1,
                  "engines": 1,
                  "max_slots": 4, "max_seq": 128, "block_size": 16,
                  "token_budget": 512}}
MIX = {"requests": 400, "concurrency": 4,
       "lengths": [{"weight": 1,
                    "prompt": {"kind": "lognormal", "median": 24,
                               "sigma": 0.6, "min": 8, "max": 60},
                    "output": {"kind": "lognormal", "median": 6,
                               "sigma": 0.4, "min": 4, "max": 12}}],
       "max_total": 128, "pool_seed": 1, "lead_in_steps": 4,
       "warmup_seed": 2, "sample_requests": 6}
# at this size the program's bf16 gap reads 0 on the sampled tokens and
# the float8 control's about 0.1-0.3 (CPU)
LIMITS = {"logit_gap": 0.05, "min_sampled_tokens": 16}
CELL = {"name": "tiny.chat", "config": "tiny", "traffic": "chat", "chips": 1}


def _run(seed=3, control=False, trace=False, seconds=3.0, log=None,
         cell=CELL, config=TINY):
    bench = {"end_to_end": [], "per_layer": [
        {"name": "window_compiles", "unit": "count"}]}
    return harness.run("tiny.chat", seed, seconds, trace,
                       t_process=time.perf_counter(), require_tpu=False,
                       bench=bench, files=(cell, config, MIX, LIMITS),
                       control=control, log=log or (lambda *a, **k: None))


def test_sound_run_is_correct_and_control_is_not():
    res = _run()
    c = res["compared"]
    assert res["correct"], c
    assert c["sampled_tokens"]["value"] >= 16
    assert res["failed"] == 0
    assert list(res)[-1] == "compared"
    # the float8 control in the program's place, same seed, same decision
    ctl = _run(control=True)
    assert not ctl["correct"]
    assert ctl["compared"]["logit_gap"]["value"] > LIMITS["logit_gap"]


def test_warm_up_meets_every_window_shape():
    # the measured server serves the warm-up's sizes in the same order:
    # nothing compiles in the window
    res = _run(seed=2**31 + 11, trace=True)
    assert res["correct"], res["compared"]
    assert res["metrics"]["window_compiles"]["value"] == 0


def test_sample_drawn_past_a_short_window():
    # a window too short for any request to finish: the server serves on
    # past the close until the sample holds enough served tokens
    lines = []
    res = _run(seed=2**31 + 12, seconds=1e-3,
               log=lambda *a, **k: lines.append(" ".join(map(str, a))))
    assert res["correct"], res["compared"]
    assert res["compared"]["sampled_tokens"]["value"] >= 16
    drain = [ln for ln in lines if ln.startswith("drain:")]
    assert drain and int(drain[0].split()[1]) > 0, lines


def test_tensor_parallel_run_is_correct(monkeypatch):
    # the same cell at tp = 2 over two devices: weights made in their
    # shardings, every step through shard_map with its all-reduces
    from repro.serving import engine
    built, real = [], engine.Engine.__init__

    def spy(self, *a, **k):
        real(self, *a, **k)
        built.append(self)

    monkeypatch.setattr(engine.Engine, "__init__", spy)
    tp2 = dict(TINY, serve=dict(TINY["serve"], tp=2))
    res = _run(seed=2**31 + 21, cell=dict(CELL, chips=2), config=tp2)
    assert res["correct"], res["compared"]
    assert res["compared"]["sampled_tokens"]["value"] >= 16
    # the warm-up's engine and the measured one, both at tp = 2, serve
    # the shards the harness made: the engine's placement moved nothing
    assert len(built) == 2 and all(e.tp == 2 for e in built)

    def buffers(e):
        return [{s.device: s.data.unsafe_buffer_pointer()
                 for s in a.addressable_shards}
                for a in jax.tree.leaves(e.params)]

    assert buffers(built[0]) == buffers(built[1])


def test_exchange_between_chips_left_out(monkeypatch):
    # tp = 2 with the all-reduces after wo and w_down dropped: each chip
    # goes on with its own partial sums
    from repro.models import attention, mlp
    for mod in (attention, mlp):
        monkeypatch.setattr(mod, "psum_if_tp", lambda x, cfg: x)
    tp2 = dict(TINY, serve=dict(TINY["serve"], tp=2))
    res = _run(seed=2**31 + 22, cell=dict(CELL, chips=2), config=tp2)
    assert not res["correct"]
    assert res["compared"]["logit_gap"]["value"] > LIMITS["logit_gap"]


@pytest.mark.parametrize("case", ["no-architecture", "unknown-architecture",
                                  "chips-not-tp"])
def test_configuration_refused_by_name(case):
    config, cell = dict(TINY), dict(CELL)
    if case == "no-architecture":
        del config["architecture"]
        want = "names no architecture"
    elif case == "unknown-architecture":
        config["architecture"] = "mamba_hybrid"
        want = "unknown architecture 'mamba_hybrid'"
    else:
        cell["chips"] = 4
        want = "asks for 4 chips"
    with pytest.raises(SystemExit, match=want):
        harness.architecture(cell, config)
    with pytest.raises(SystemExit, match=want):
        _run(cell=cell, config=config)


def test_architecture_loaded_by_name():
    arch = harness.load_architecture("dense_gqa")
    assert arch is harness.architecture(CELL, TINY)
    for name in ("dims", "program_config", "make_params", "Reference",
                 "token_flops", "chunk_flops", "attn_least_time"):
        assert callable(getattr(arch, name)), name
    with pytest.raises(SystemExit, match="unknown architecture"):
        harness.load_architecture("../harness")


def test_token_altered_where_produced(monkeypatch):
    from repro.serving import engine
    real = engine.d2h

    def altered(x):
        out = real(x)
        return (out + 1) % TINY["vocab_size"] if out.dtype.kind == "i" \
            else out

    monkeypatch.setattr(engine, "d2h", altered)
    res = _run()
    assert not res["correct"]
    assert res["compared"]["logit_gap"]["value"] > LIMITS["logit_gap"]


def test_step_returns_state_unchanged(monkeypatch):
    import jax
    from repro.serving import engine
    real = engine.attn_call

    def stale(fn, params, cache, *args, **kw):
        out = real(fn, params, cache, *args, **kw)
        want = jax.tree.structure(cache)
        return tuple(cache if jax.tree.structure(o) == want else o
                     for o in out)

    monkeypatch.setattr(engine, "attn_call", stale)
    res = _run()
    assert not res["correct"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "smollm-360m.chat-closed-loop", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=240)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _command(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_name_has_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        cell, cfg, _, _ = harness.load_cell(bench, w["name"])
        harness.architecture(cell, cfg).dims(cfg)
        assert harness.load_metric_readers(bench, w["name"])
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
