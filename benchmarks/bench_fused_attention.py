"""Fused mixed-iteration attention + int8 KV blocks: the one-launch-per-
step benchmark (DESIGN.md §Fused mixed-iteration attention, §Quantized KV
blocks).

Scenario — the hetero longtail mix the fused kernel exists for: a decode
batch whose context lengths spread ~100x is streaming tokens while a long
prompt chunks through the same engine. The separate-kernel engine issues
TWO attention-bearing device calls per mixed step (chunk batch + decode
batch), each padding its own pow2 work bucket; the fused engine packs
both into ONE tagged work list — one call, one launch per layer, the
same two padding tails (buckets stay split: pow2(dec)+pow2(ck), since
a merged pow2 bucket can overshoot the pair). Measures, per engine:

  * wall time per mixed step (median over the long prompt's chunk steps),
  * attention-bearing device calls per mixed step, via the engine's
    ``attn_call`` launch-count shim (trace-time counters can't see
    launches inside jit) — fused MUST be exactly 1, separate exactly 2,
  * greedy-token parity between the two engines (bf16: bit-identical),
  * int8 KV residency from REAL array bytes: resident requests at equal
    pool bytes must be >= 1.8x bf16 (the (Dp+4)/(2·Dp) layout bound,
    Dp = the lane-padded row width).

Emits BENCH_fused_attention.json at the repo root. Asserted acceptance:
fused mixed-step time strictly below the two-launch baseline, exactly one
attention call per fused mixed step, int8 residency >= 1.8x, bf16 tokens
identical across backends. Off-TPU the kernels run in Pallas interpret
mode, whose per-grid-step Python overhead prices neither launches nor DMA
— there the strict mixed-step-time assertion uses the analytic kernel
mirror (``kernels.cost.mixed_iter_time_s``, fused vs flat on the SAME
workload shape; bench_decode_hotloop's "would run" precedent) and the
measured interpret-mode walls are reported unasserted.

Run: PYTHONPATH=src python benchmarks/bench_fused_attention.py
     [--long-prompt 2048] [--budget 64] [--decode-reqs 5]
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

try:
    from benchmarks.common import write_artifact
except ImportError:                     # run as a plain script
    from common import write_artifact

import jax
import numpy as np

import repro.serving.engine as engine_mod
from repro.configs import get_config
from repro.core.migration import kv_bytes
from repro.kernels.cost import AttnSpec, mixed_iter_time_s
from repro.models import build_model
from repro.serving.engine import DEFAULT_BLOCK_SIZE, Engine
from repro.serving.request import ServeRequest


def run_scenario(model, params, *, backend, kv_dtype, long_prompt, budget,
                 decode_reqs, seed=0):
    """Decode batch at ~100x context spread + one long chunking prompt.
    Returns per-mixed-step timings, attention calls per mixed step, and
    the decode requests' greedy streams."""
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(seed)
    # ~100x spread, none block-aligned — the heterogeneity the flat work
    # list amortizes and padded grids pay for
    plens = np.geomspace(7, 700, decode_reqs).astype(int)
    max_seq = 1 << int(long_prompt + 64).bit_length()
    eng = Engine(0, model, params, max_slots=decode_reqs + 1,
                 max_seq=max_seq,
                 token_budget=long_prompt + 512 + int(plens.sum()) + 4096,
                 attn_backend=backend, kv_dtype=kv_dtype,
                 prefill_token_budget=budget)
    decode = [ServeRequest(i, rng.integers(0, vocab, int(p))
                           .astype(np.int32),
                           8 + long_prompt // max(budget, 1))
              for i, p in enumerate(plens)]
    for r in decode:
        eng.submit(r)
    while any(r.prefilling or r.state.name == "WAITING" for r in decode):
        eng.step()
    for _ in range(4):                  # decode batch in steady state
        eng.step()
    long_req = ServeRequest(99, rng.integers(0, vocab, long_prompt)
                            .astype(np.int32), 2)
    eng.submit(long_req)
    step_s, calls = [], []
    while long_req.prefilling or long_req.first_token_step is None:
        c0 = engine_mod.ATTN_CALLS
        t0 = time.perf_counter()
        eng.step()
        jax.block_until_ready(eng.cache)
        step_s.append(time.perf_counter() - t0)
        calls.append(engine_mod.ATTN_CALLS - c0)
    while any(r.finish_step is None for r in decode):
        eng.step()
    # mixed steps = chunk work beside a live decode batch; drop compile
    # steps (table-width/chunk-bucket retraces) via the median
    return {
        "backend": backend,
        "kv_dtype": kv_dtype,
        "mixed_steps": len(step_s),
        "step_s_median": float(np.median(step_s)),
        "step_s_mean": float(np.mean(step_s)),
        "attn_calls_per_mixed_step": float(np.mean(calls)),
        "attn_calls_max": int(np.max(calls)),
        "tokens": {r.req_id: list(r.generated) for r in decode},
    }


def residency(model, block_size=16, num_blocks=64):
    """Resident-request ratio at EQUAL pool bytes, from real array bytes:
    how many int8 blocks fit in one full-precision pool's footprint.
    The asserted ``resident_ratio_vs_bf16`` normalizes the full pool to
    bf16 width (the reduced CPU model keeps f32 pools, which would
    overstate the win) — the layout bound is 2·Dp/(Dp+4)."""
    full = model.init_paged_cache(num_blocks, block_size)
    int8 = model.init_paged_cache(num_blocks, block_size, kv_dtype="int8")
    b_full, b_int8 = kv_bytes(full), kv_bytes(int8)
    itemsize = jax.tree.leaves(full)[0].dtype.itemsize
    return {
        "full_pool_bytes": int(b_full),
        "full_pool_itemsize": int(itemsize),
        "int8_pool_bytes": int(b_int8),
        "resident_ratio_raw": b_full / b_int8,
        "resident_ratio_vs_bf16": (b_full / b_int8) * 2.0 / itemsize,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--long-prompt", type=int, default=2048)
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--decode-reqs", type=int, default=5)
    args = ap.parse_args()

    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    out = {"config": {"arch": cfg.name, "long_prompt": args.long_prompt,
                      "budget": args.budget,
                      "decode_reqs": args.decode_reqs,
                      "jax_backend": jax.default_backend()}}
    kw = dict(long_prompt=args.long_prompt, budget=args.budget,
              decode_reqs=args.decode_reqs)
    # warmup pass populates each engine's jit caches at identical shapes
    for mode, backend, kvd in (("fused", "fused", "bf16"),
                               ("separate", "flat", "bf16"),
                               ("fused_int8", "fused", "int8")):
        run_scenario(model, params, backend=backend, kv_dtype=kvd, **kw)
        out[mode] = run_scenario(model, params, backend=backend,
                                 kv_dtype=kvd, **kw)
        print(f"-- {mode:10s} mixed-step median "
              f"{out[mode]['step_s_median']*1e3:7.2f} ms  "
              f"attn calls/step {out[mode]['attn_calls_per_mixed_step']:.2f}")

    fused, sep = out["fused"], out["separate"]
    # one-launch contract: EVERY fused mixed step made exactly one
    # attention-bearing device call; the separate path makes two
    assert fused["attn_calls_max"] == 1, \
        f"fused mixed step made {fused['attn_calls_max']} attention calls"
    assert sep["attn_calls_per_mixed_step"] == 2.0, \
        f"baseline made {sep['attn_calls_per_mixed_step']} calls/step"
    # greedy parity: fusing reshapes launches, never bf16 token values
    assert fused["tokens"] == sep["tokens"], "bf16 greedy parity broken"
    speedup = sep["step_s_median"] / max(fused["step_s_median"], 1e-12)
    out["mixed_step_speedup"] = speedup
    # analytic kernel mirror of the SAME mixed-iteration shape: the decode
    # batch mid-longtail plus one budget-sized chunk halfway through the
    # long prompt — one launch over the real items vs two padded ones
    spec = AttnSpec(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                    block_s=DEFAULT_BLOCK_SIZE)
    plens = np.geomspace(7, 700, args.decode_reqs).astype(int)
    chunks = [(args.budget, args.long_prompt // 2)]
    t_fused = mixed_iter_time_s(chunks, list(plens), spec,
                                decode_backend="fused")
    t_sep = mixed_iter_time_s(chunks, list(plens), spec,
                              decode_backend="flat")
    out["analytic"] = {"fused_s": t_fused, "separate_s": t_sep,
                       "speedup": t_sep / t_fused}
    on_tpu = jax.default_backend() == "tpu"
    out["measured_assert"] = on_tpu
    assert t_fused < t_sep, \
        f"analytic fused not faster: {t_fused:.3e} vs {t_sep:.3e} s"
    if on_tpu:
        assert fused["step_s_median"] < sep["step_s_median"], \
            f"fused not faster: {fused['step_s_median']*1e3:.2f} ms vs " \
            f"{sep['step_s_median']*1e3:.2f} ms"
        print(f"fused mixed step {speedup:.2f}x the two-launch baseline "
              f"({sep['step_s_median']*1e3:.2f} -> "
              f"{fused['step_s_median']*1e3:.2f} ms)")
    else:
        print(f"off-TPU (interpret mode): analytic mixed step "
              f"{t_sep/t_fused:.2f}x below the two-launch baseline "
              f"({t_sep*1e6:.1f} -> {t_fused*1e6:.1f} us); measured "
              f"interpret walls reported unasserted")

    res = residency(model)
    out["residency"] = res
    assert res["resident_ratio_vs_bf16"] >= 1.8, \
        f"int8 residency only {res['resident_ratio_vs_bf16']:.2f}x vs bf16"
    print(f"int8 KV: {res['resident_ratio_vs_bf16']:.2f}x resident "
          f"requests at equal pool bytes vs bf16 (>= 1.8x required; "
          f"{res['resident_ratio_raw']:.2f}x vs this host's "
          f"{res['full_pool_itemsize']}-byte pools)")
    for k in ("fused", "separate", "fused_int8"):
        out[k].pop("tokens")

    print("wrote", write_artifact("fused_attention", out))


def run():
    """CSV rows for benchmarks.run."""
    main()
    import json
    doc = json.loads((Path(__file__).resolve().parent.parent
                      / "BENCH_fused_attention.json").read_text())
    d = doc["data"]
    return [
        {"name": "fused_mixed_step",
         "us_per_call": d["fused"]["step_s_median"] * 1e6,
         "derived": f"calls_per_step={d['fused']['attn_calls_per_mixed_step']}"},
        {"name": "separate_mixed_step",
         "us_per_call": d["separate"]["step_s_median"] * 1e6,
         "derived": f"speedup={d['mixed_step_speedup']:.3g};"
                    f"int8_residency="
                    f"{d['residency']['resident_ratio_vs_bf16']:.3g}"},
    ]


if __name__ == "__main__":
    main()
