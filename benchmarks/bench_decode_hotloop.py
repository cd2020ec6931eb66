"""Decode hot loop: device-resident engine vs. the host-driven loop.

For the same 16-way request mix — heterogeneous (128x prompt-length
spread) and uniform (same total tokens) — this measures, per decode step:

  * wall time / steps-per-second (after jit warmup),
  * device→host synchronizations (counted through `engine.d2h`). The
    host loop's sampling is already fused to one sync per decode step
    (this PR); its remaining tax is host-driven state — per-step
    block-table rebuild + upload and per-prefill syncs — which the
    device-resident loop removes, and `step(burst=n)` amortizes the one
    remaining sync across n fused steps,
  * grid accounting (acceptance): the flat grid runs Σ_b ceil(L_b/BS)
    work items (± pow2 bucket padding) where the padded grid ran
    B·max_b ceil(L_b/BS).

Emits BENCH_decode_hotloop.json at the repo root.

Run: PYTHONPATH=src python benchmarks/bench_decode_hotloop.py
     [--new-tokens N] [--burst B] [--backend dense|grid|flat]
"""
from __future__ import annotations

import argparse
import json
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
from repro.kernels.cost import pow2_bucket
from repro.models import build_model
from repro.serving.block_pool import blocks_for
from repro.serving.engine import Engine
from repro.serving.request import ServeRequest

MAX_SEQ = 256
BLOCK_SIZE = 16
# 16-way heterogeneous: 128x spread, the regime of PAPER.md Fig. 2
HETERO = [2, 2, 3, 4, 4, 6, 8, 8, 12, 16, 24, 32, 48, 64, 96, 120]
UNIFORM = [sum(HETERO) // len(HETERO)] * len(HETERO)


def serve(model, params, prompts, new_tokens, *, device_resident, burst,
          backend):
    eng = Engine(0, model, params, max_slots=len(prompts), max_seq=MAX_SEQ,
                 paged=True, block_size=BLOCK_SIZE,
                 device_resident=device_resident, attn_backend=backend,
                 # one-step admission: this bench measures the decode hot
                 # loop, so the whole mix must enter (and finish) together
                 prefill_token_budget=sum(prompts) + len(prompts))

    def drain(measure: bool):
        rng = np.random.default_rng(0)
        reqs = [ServeRequest(i, rng.integers(0, model.cfg.vocab_size, p)
                             .astype(np.int32), new_tokens)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.step(burst)        # admission + prefill: excluded from timing
        d2h0, steps0 = engine_mod.D2H_CALLS, eng.steps
        items0, real0 = eng.work_items, eng.real_work_items
        t0 = time.perf_counter()
        while any(r.finish_step is None for r in reqs):
            items0, real0 = eng.work_items, eng.real_work_items
            eng.step(burst)
        dt = time.perf_counter() - t0
        # work-list accounting of the final decode launch
        grid = ({"flat_items": eng.work_items - items0,
                 "real_items": eng.real_work_items - real0}
                if eng.work_items > items0 else {})
        return dt, eng.steps - steps0, engine_mod.D2H_CALLS - d2h0, grid

    drain(measure=False)             # jit warmup: identical request mix
    dt, steps, syncs, grid = drain(measure=True)  # warm caches, decode-only
    steps = max(steps, 1)
    return {
        "decode_step_ms": dt / steps * 1e3,
        "steps_per_s": steps / dt,
        "host_syncs_per_step": syncs / steps,
        "grid": grid,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--burst", type=int, default=8)
    ap.add_argument("--backend", default=None,
                    choices=["dense", "grid", "flat"])
    args = ap.parse_args()

    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    out = {"config": {"arch": cfg.name, "max_seq": MAX_SEQ,
                      "block_size": BLOCK_SIZE, "new_tokens": args.new_tokens,
                      "burst": args.burst, "backend": args.backend or "auto",
                      "jax_backend": jax.default_backend()}}
    for name, prompts in (("hetero", HETERO), ("uniform", UNIFORM)):
        paths = {
            "old_host_loop": dict(device_resident=False, burst=1,
                                  backend="dense"),
            "new_device_loop": dict(device_resident=True, burst=1,
                                    backend=args.backend),
            "new_device_burst": dict(device_resident=True, burst=args.burst,
                                     backend=args.backend),
        }
        res = {k: serve(model, params, prompts, args.new_tokens, **kw)
               for k, kw in paths.items()}
        out[name] = res
        print(f"-- {name}: prompts {prompts}")
        for k, r in res.items():
            print(f"   {k:18s} step {r['decode_step_ms']:8.2f} ms   "
                  f"host syncs/step {r['host_syncs_per_step']:5.2f}   "
                  f"grid {r['grid'] or '-'}")

    # acceptance: flat work count == Σ ceil(L_b/BS) (± pow2 bucket) on the
    # 16-way hetero batch, vs B·max_b ceil(L_b/BS) for the padded grid.
    # All 16 requests share max_new, so the final decode step (whose grid
    # accounting `serve` captured) sees lengths p + new_tokens - 1.
    g = out["hetero"]["new_device_loop"]["grid"]
    final = [p + args.new_tokens - 1 for p in HETERO]
    real = sum(blocks_for(l, BLOCK_SIZE) for l in final)
    padded = len(HETERO) * max(blocks_for(l, BLOCK_SIZE) for l in final)
    assert g["real_items"] == real, (g, real)
    assert g["flat_items"] == pow2_bucket(real), g
    assert g["flat_items"] <= padded / 2, g
    # acceptance: the device loop makes exactly one sync per step
    for name in ("hetero", "uniform"):
        assert out[name]["new_device_loop"]["host_syncs_per_step"] <= 1.0 + 1e-9
        assert out[name]["old_host_loop"]["host_syncs_per_step"] >= 1.0
    ratio = padded / g["flat_items"]
    backend = args.backend or "auto"
    ran = ("ran" if backend == "flat"
           else f"would run (this run used backend={backend})")
    print(f"flat grid {ran}: {g['flat_items']} items "
          f"(Σ ceil = {g['real_items']}) vs padded {padded}  "
          f"-> {ratio:.1f}x fewer block iterations on the hetero batch")

    print("wrote", write_artifact("decode_hotloop", out))


if __name__ == "__main__":
    main()
