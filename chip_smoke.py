"""Chip smoke test: the serving main path, at published width, on a TPU.

    python3 chip_smoke.py               # one chip: smollm-360m, bf16
    python3 chip_smoke.py --four-chips  # four chips: qwen2.5-14b at tp=4

One chip (the default). Builds a two-engine MILS cluster through
``repro.launch.serve.build_server`` behind the ``cascade`` policy, serving
smollm-360m at its published width (32 layers, d_model 960, vocab 49152)
in bf16 with random weights from ``--seed`` and the default engine
options: paged pool, chunked prefill, fused mixed step, prefix cache. It
serves eight requests (prompts of 64-2048 tokens, 32-128 new tokens), so
that mixed steps and a length-stage handover happen, then serves the same
requests again on the same cluster built with ``attn_backend="dense"``.
It checks that every request finishes on both, and that each prompt's
first-step logits from the two agree within a bf16 tolerance, with the
same greedy token wherever the top-2 margin exceeds that tolerance.

Four chips (``--four-chips``, and nothing else). Serves qwen2.5-14b in
bf16 at tp=4, its weights initialised straight into their shardings, then
compares an 8-layer cut of it at tp=4 against the same weights at tp=1 on
one chip: served greedy tokens and first-step logits must agree.

It refuses to run off a TPU, on a non-fused or interpreted attention
backend, or with ``REPRO_PAGED_ATTN`` set. The last line of standard
output is ``{"ok": true, "device": {...}}``; a failure raises and exits
non-zero before printing it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.serve import (build_server, enable_compile_cache,  # noqa: E402
                                init_params)
from repro.models import build_model  # noqa: E402
from repro.serving.request import ServeRequest  # noqa: E402
from repro.serving.server import ServerConfig  # noqa: E402

# bf16 agreement bound between two attention paths on the same weights:
# |a - b| <= LOGIT_RTOL * max|b| over the vocabulary. bf16 keeps 8
# mantissa bits (relative step 2^-8 ~ 0.004); 0.05 leaves room for that
# rounding compounding through the layer stack, and still catches a wrong
# block, head or mask, which moves logits by O(max|b|).
LOGIT_RTOL = 0.05

# one chip: (prompt tokens, new tokens). Stage 0 of the two-engine plan
# ends at MAX_SEQ / 4 = 576 tokens; the 568-token prompt crosses it in
# its first decode steps, before the adaptive refiner first moves the
# boundary (every 16 steps), so it is handed over to stage 1.
ONE_CHIP_REQUESTS = [(64, 128), (180, 96), (520, 128), (568, 96),
                     (900, 32), (1300, 48), (1700, 32), (2048, 64)]
MAX_SEQ = 2304
MAX_SLOTS = 8

# four chips: a few requests per phase, short enough that every prompt
# prefills in one or two chunks (few compiled shapes: four-chip time is
# charged four times)
FOUR_CHIP_REQUESTS = [(96, 16), (200, 16), (400, 16)]
FOUR_CHIP_MAX_SEQ = 512
CUT_LAYERS = 8


class _CompileClock:
    """Programs XLA compiled in this process and the seconds it took
    (JAX's own event)."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1


def _requests(shapes, vocab, seed):
    rng = np.random.default_rng(seed)
    return [ServeRequest(i, rng.integers(0, vocab, p).astype(np.int32), n)
            for i, (p, n) in enumerate(shapes)]


def _copy(reqs):
    return [ServeRequest(r.req_id, r.prompt.copy(), r.max_new_tokens)
            for r in reqs]


def _check_engines(srv, backend):
    for e in srv.engines:
        if e.attn_backend != backend or e.attn_interpret:
            raise RuntimeError(
                f"engine {e.id}: backend {e.attn_backend!r} interpret="
                f"{e.attn_interpret}, expected compiled {backend!r}")
        if not (e.paged and e.chunked_prefill and e.prefix_cache):
            raise RuntimeError(f"engine {e.id}: not on the default path")
        if backend == "fused" and not e.fused_mixed:
            raise RuntimeError(f"engine {e.id}: fused mixed step is off")


def _serve(srv, reqs, label):
    """Serve ``reqs`` to completion; every one must finish in full."""
    t0 = time.perf_counter()
    srv.run(reqs, max_steps=4000)
    wall = time.perf_counter() - t0
    done = {r.req_id: r for r in srv.finished}
    for r in reqs:
        f = done.get(r.req_id)
        if f is None or f.rejected or f.failed:
            raise RuntimeError(f"{label}: request {r.req_id} did not finish")
        if len(f.generated) != r.max_new_tokens:
            raise RuntimeError(
                f"{label}: request {r.req_id} made {len(f.generated)} of "
                f"{r.max_new_tokens} tokens")
    tokens = sum(len(done[r.req_id].generated) for r in reqs)
    return wall, tokens, {r.req_id: list(done[r.req_id].generated)
                          for r in reqs}


def _compare_logits(got, ref, label):
    """bf16 agreement of two first-step logit vectors; returns
    (relative max error, whether the greedy token was compared)."""
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise RuntimeError(f"{label}: non-finite logits")
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    if err > LOGIT_RTOL * scale:
        raise RuntimeError(f"{label}: max |diff| {err:.4g} > "
                           f"{LOGIT_RTOL} * max|ref| {scale:.4g}")
    top2 = np.sort(ref)[-2:]
    decisive = float(top2[1] - top2[0]) > 2 * err
    if decisive and int(np.argmax(got)) != int(np.argmax(ref)):
        raise RuntimeError(f"{label}: greedy token differs")
    return err / scale, decisive


def _compare_paths(eng, ref_eng, reqs, label):
    worst, decisive = 0.0, 0
    for r in reqs:
        rel, dec = _compare_logits(eng.prompt_logits(r.prompt),
                                   ref_eng.prompt_logits(r.prompt),
                                   f"{label} request {r.req_id}")
        worst = max(worst, rel)
        decisive += dec
    print(f"{label}: first-step logits max |diff| / max|ref| = {worst:.3g} "
          f"(bound {LOGIT_RTOL}); greedy token equal on {decisive} of "
          f"{len(reqs)} prompts with a decisive margin")


def _gib_per_device(tree):
    """GiB of ``tree``'s shards on each device (a function of its own, so
    no loop variable keeps a shard alive once the caller drops ``tree``)."""
    on_dev = {d: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            on_dev[sh.device] += sh.data.nbytes
    return [round(b / 2**30, 2) for b in on_dev.values()]


def _same_first_tokens(a, b):
    return sum(a[i][0] == b[i][0] for i in a)


def one_chip(seed: int, clock: _CompileClock) -> None:
    cfg = dataclasses.replace(get_config("smollm-360m"), dtype=jnp.bfloat16)
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} vocab={cfg.vocab_size} "
          f"dtype={jnp.dtype(cfg.dtype).name}")
    params = init_params(build_model(cfg), seed)
    reqs = _requests(ONE_CHIP_REQUESTS, cfg.vocab_size, seed)
    common = dict(engines=2, max_seq=MAX_SEQ, max_slots=MAX_SLOTS,
                  params=params)
    srv = build_server(cfg, ServerConfig(policy="cascade", seed=seed),
                       **common)
    _check_engines(srv, "fused")
    e0 = srv.engines[0]
    print(f"backend: {e0.attn_backend} interpret={e0.attn_interpret} "
          f"kv_dtype={e0.kv_dtype} block_size={e0.block_size} "
          f"prefill_budget={e0.prefill_token_budget}")
    c0, n0 = clock.seconds, clock.programs
    wall, tokens, fused_toks = _serve(srv, _copy(reqs), "fused")
    compile_s, programs = clock.seconds - c0, clock.programs - n0
    mixed = sum(e.mixed_steps for e in srv.engines)
    print(f"fused: finished {len(reqs)}/{len(reqs)} requests, {tokens} "
          f"tokens, {srv.migrations} migrations, {mixed} mixed steps, "
          f"{srv.steps} server steps")
    print(f"fused: serve wall {wall:.2f} s, of which XLA compile "
          f"{compile_s:.2f} s for {programs} programs")
    if srv.migrations < 1:
        raise RuntimeError("no length-stage handover happened")
    if mixed < 1:
        raise RuntimeError("no mixed decode + prefill step ran")

    ref = build_server(cfg, ServerConfig(policy="cascade", seed=seed,
                                         attn_backend="dense"), **common)
    _check_engines(ref, "dense")
    wall_d, tokens_d, dense_toks = _serve(ref, _copy(reqs), "dense")
    print(f"dense: finished {len(reqs)}/{len(reqs)} requests, {tokens_d} "
          f"tokens, serve wall {wall_d:.2f} s; first token equal to fused "
          f"on {_same_first_tokens(fused_toks, dense_toks)} of {len(reqs)}")
    _compare_paths(srv.engines[0], ref.engines[0], reqs, "fused vs dense")


def four_chips(seed: int, clock: _CompileClock) -> None:
    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, have "
                           f"{len(jax.devices())}")
    cfg = dataclasses.replace(get_config("qwen2.5-14b"), dtype=jnp.bfloat16)
    reqs = _requests(FOUR_CHIP_REQUESTS, cfg.vocab_size, seed)
    opts = dict(engines=1, max_seq=FOUR_CHIP_MAX_SEQ, max_slots=4)
    sc = ServerConfig(policy="cascade", seed=seed)

    # full depth at tp=4, weights created in their shardings
    params = init_params(build_model(cfg), seed, tp=4)
    print(f"model: {cfg.name} layers={cfg.num_layers} tp=4, weights per "
          f"chip {_gib_per_device(params)} GiB")
    srv = build_server(cfg, sc, tp=4, params=params, **opts)
    _check_engines(srv, "fused")
    c0 = clock.seconds
    wall, tokens, _ = _serve(srv, _copy(reqs), "tp=4")
    print(f"tp=4: finished {len(reqs)}/{len(reqs)} requests, {tokens} "
          f"tokens, serve wall {wall:.2f} s, of which XLA compile "
          f"{clock.seconds - c0:.2f} s")
    del srv, params
    gc.collect()

    # depth-cut copy: the same weights at tp=4 and at tp=1 on one chip
    cut = dataclasses.replace(cfg, num_layers=CUT_LAYERS)
    p4 = init_params(build_model(cut), seed, tp=4)
    s4 = build_server(cut, sc, tp=4, params=p4, **opts)
    _check_engines(s4, "fused")
    _, _, toks4 = _serve(s4, _copy(reqs), "cut tp=4")
    p1 = jax.device_put(p4, jax.devices()[0])
    s1 = build_server(cut, sc, tp=1, params=p1, **opts)
    _check_engines(s1, "fused")
    _, _, toks1 = _serve(s1, _copy(reqs), "cut tp=1")
    same = sum(toks4[i] == toks1[i] for i in toks4)
    print(f"{CUT_LAYERS}-layer cut: greedy tokens identical at tp=4 and "
          f"tp=1 on {same} of {len(reqs)} requests; first token equal on "
          f"{_same_first_tokens(toks4, toks1)}")
    _compare_paths(s4.engines[0], s1.engines[0], reqs, "tp=4 vs tp=1")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the qwen2.5-14b tensor-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if "REPRO_PAGED_ATTN" in os.environ:
        raise SystemExit("chip_smoke: REPRO_PAGED_ATTN must not be set")
    if jax.default_backend() != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX backend is "
                         f"{jax.default_backend()!r})")
    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile cache={cache} ({warm} entries at start)")
    clock = _CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed, clock)
    else:
        one_chip(args.seed, clock)
    print(f"total wall {time.perf_counter() - t0:.2f} s, XLA compile "
          f"{clock.seconds:.2f} s for {clock.programs} programs")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
