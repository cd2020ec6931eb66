"""The harness at tensor parallelism 4, on four virtual CPU devices, shaped
like ``qwen2.5-14b.tp4-chat``: 8 KV heads so that each device holds 2,
QKV bias, an untied head and a vocabulary divisible by 4, served by one
engine through ``harness.run`` with every per-layer reader of that cell.
A sound run is correct and its collective readers read; the same run with
the all-reduces after ``wo`` and ``w_down`` dropped is not correct.

The suite's conftests give two virtual devices, so each run is a process
of its own with four (``python3 test_bench_tp4.py <case>``), under a
timeout of its own. On the CPU the XLA ops run on the host's threads, not
on a device plane: the child reads each such thread's ops as one device's,
under the names the chip gives them, so that the device-trace readers
have ops to read."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CELL_NAME = "qwen2.5-14b.tp4-chat"
TINY4 = {"name": "tiny4", "architecture": "dense_gqa", "model_type": "qwen2",
         "hidden_size": 128, "intermediate_size": 192,
         "num_attention_heads": 16, "num_key_value_heads": 8,
         "num_hidden_layers": 2, "vocab_size": 256,
         "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
         "rope_theta": 10000.0, "tie_word_embeddings": False,
         "serve": {"dtype": "bfloat16", "policy": "cascade", "tp": 4,
                   "engines": 1, "max_slots": 4, "max_seq": 128,
                   "block_size": 16, "token_budget": 512}}
# the small mix and short window of the tp = 2 case in test_bench_harness
MIX = {"requests": 400, "concurrency": 4,
       "lengths": [{"weight": 1,
                    "prompt": {"kind": "lognormal", "median": 24,
                               "sigma": 0.6, "min": 8, "max": 60},
                    "output": {"kind": "lognormal", "median": 6,
                               "sigma": 0.4, "min": 4, "max": 12}}],
       "max_total": 128, "pool_seed": 1, "lead_in_steps": 4,
       "warmup_seed": 2, "sample_requests": 6}
LIMITS = {"logit_gap": 0.05, "min_sampled_tokens": 16}
CELL = {"name": CELL_NAME, "config": "tiny4", "traffic": "chat", "chips": 4}
CHILD_TIMEOUT_S = 600


# the CPU client names a collective after its JAX primitive
# (``psum_invariant.7``); the chip's trace names it by its HLO op
# (``all-reduce.7``), which is what ``collective_share`` matches
CHIP_NAMES = {"psum": "all-reduce", "psum_invariant": "all-reduce",
              "all_gather": "all-gather",
              "all_gather_invariant": "all-gather"}


def _chip_name(name: str) -> str:
    base, num = name.rsplit(".", 1)
    return f"{CHIP_NAMES.get(base, base)}.{num}"


def _with_cpu_ops(load):
    """``xtrace.load`` that also takes the XLA ops the CPU client ran on
    the host's threads, one thread's ops as one device's, each under the
    name the chip's trace gives it."""
    import re

    import xtrace
    from jax.profiler import ProfileData
    op = re.compile(r"^[A-Za-z_][\w\-]*\.\d+$")

    def cpu_load(path):
        tr = load(path)
        ops, lines = [], 0
        for plane in ProfileData.from_file(path).planes:
            if plane.name != xtrace.HOST_PLANE:
                continue
            for line in plane.lines:
                if not line.name.startswith("tf_XLA"):
                    continue
                mine = [(_chip_name(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns, lines)
                        for ev in line.events if op.match(ev.name)]
                if mine:
                    ops += mine
                    lines += 1
        return xtrace.Trace(tr.ops + ops, tr.spans, tr.chips + lines,
                            tr.planes)

    return cpu_load


def _child(case: str) -> None:
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    import xtrace
    if case == "psum-dropped":
        from repro.models import attention, mlp
        for mod in (attention, mlp):
            mod.psum_if_tp = lambda x, cfg: x
    xtrace.load = _with_cpu_ops(xtrace.load)
    res = harness.run(CELL_NAME, 2**31 + 41, 3.0, True,
                      t_process=time.perf_counter(), require_tpu=False,
                      bench=harness.load_bench(),
                      files=(CELL, TINY4, MIX, LIMITS),
                      log=lambda *a, **k: None)
    print(json.dumps(res), flush=True)


def _serve(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(Path(__file__)), case],
                       cwd=HERE, env=env, capture_output=True, text=True,
                       timeout=CHILD_TIMEOUT_S)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_tp4_run_is_correct_and_collectives_read():
    res = _serve("sound")
    assert res["correct"], res["compared"]
    assert res["device"]["count"] == 4
    assert res["compared"]["sampled_tokens"]["value"] >= 16
    got = res["metrics"]
    assert got["collective_share"]["value"] > 0, got
    assert got["collective_bytes_per_token"]["value"] > 0, got
    assert got["window_compiles"]["value"] == 0


def test_tp4_exchange_between_chips_left_out():
    res = _serve("psum-dropped")
    assert not res["correct"]
    assert res["compared"]["logit_gap"]["value"] > LIMITS["logit_gap"]


if __name__ == "__main__":
    _child(sys.argv[1])
