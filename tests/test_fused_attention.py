"""Fused mixed-iteration attention + quantized KV blocks (DESIGN.md
§Fused mixed-iteration attention, §Quantized KV blocks): the one-launch
kernel vs. the two-kernel reference and the dense oracle on mixed batches
at 128x length spread — dead slots, aliased prefix blocks, interleaved
tags — the device-counted grid (exact item counts, an empty call, one
program across counts), int8 bounded error, engine greedy bit-parity,
the one-attention-call and one-d2h-per-mixed-step contracts, one mixed
program across batch make-ups, and the cost mirror."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serving.engine as engine_mod
from repro.configs import get_config
from repro.kernels.cost import (AttnSpec, LAUNCH_OVERHEAD_S,
                                SKIP_OVERHEAD_S, kv_bytes_per_elem,
                                mixed_iter_time_s, pow2_bucket)
from repro.kernels.decode_attention import paged_decode_attention_flat
from repro.kernels.mixed_attention import paged_mixed_attention
from repro.kernels.prefill_attention import paged_prefill_attention
from repro.kernels.ref import decode_attention_ref
from repro.models import build_model
from repro.models.attention import (KVCache, dequantize_piece,
                                    quantize_kv, quantize_piece,
                                    resolve_paged_backend)
from repro.serving.block_pool import blocks_for
from repro.serving.engine import Engine
from repro.serving.request import ServeRequest

RNG = np.random.default_rng(23)


# --------------------------------------------------------------------------
# Kernel: fused work list vs. the two kernels it replaces
# --------------------------------------------------------------------------
def _mixed_case(segs, C, H, Hkv, Dh, BS, dtype, alias=None, nbt=None):
    """Build one mixed iteration. ``segs``: ``("dec", L)`` is a decode row
    whose cache holds L tokens (ctx = L-1, seg = 1; L = 0 is a dead slot
    contributing zero work items) and ``("ck", ctx, clen)`` a prefill
    chunk. ``alias=(i, j, nb)`` makes segments i and j share their first
    ``nb`` physical blocks (prefix-cache aliasing); ``nbt`` sets the
    table width (default: one column past the longest segment). Returns
    the fused operands plus each segment's contiguous K/V for the
    oracle."""
    B = len(segs)
    totals = [(s[1] if s[0] == "dec" else s[1] + s[2]) for s in segs]
    NBT = nbt or max(max(-(-t // BS) for t in totals), 1) + 1
    NB = sum(-(-t // BS) for t in totals) + 3
    perm = RNG.permutation(NB)
    k_pool = np.zeros((NB, Hkv, BS, Dh), np.float32)  # head-major pool
    v_pool = np.zeros_like(k_pool)
    bt = np.full((B, NBT), NB - 1, np.int32)
    full = []
    pi = 0
    for s, t in enumerate(totals):
        kk = RNG.normal(0, 1, (NBT * BS, Hkv, Dh)).astype(np.float32)
        vv = RNG.normal(0, 1, (NBT * BS, Hkv, Dh)).astype(np.float32)
        if alias and s == alias[1]:
            n = alias[2] * BS
            kk[:n], vv[:n] = full[alias[0]][0][:n], full[alias[0]][1][:n]
        full.append((kk, vv))
        for j in range(-(-t // BS)):
            if alias and s == alias[1] and j < alias[2]:
                bt[s, j] = bt[alias[0], j]       # shared prefix block
                continue
            pb = int(perm[pi]); pi += 1
            bt[s, j] = pb
            k_pool[pb] = kk[j * BS:(j + 1) * BS].swapaxes(0, 1)
            v_pool[pb] = vv[j * BS:(j + 1) * BS].swapaxes(0, 1)
    q = RNG.normal(0, 1, (B, C, H, Dh)).astype(np.float32)
    ctx = np.asarray([s[1] - 1 if s[0] == "dec" else s[1] for s in segs],
                     np.int32)
    seg = np.asarray([1 if s[0] == "dec" else s[2] for s in segs], np.int32)
    tags = np.asarray([0 if s[0] == "dec" else 1 for s in segs], np.int32)
    to = lambda a: jnp.asarray(a, dtype)
    return (to(q), to(k_pool), to(v_pool), jnp.asarray(bt),
            jnp.asarray(ctx), jnp.asarray(seg), jnp.asarray(tags), full)


# interleaved tags, 128x total-context spread (4..512), a dead slot, and
# two decode rows sharing their first prefix block
SEGS = [("dec", 4), ("ck", 48, 17), ("dec", 512), ("dec", 0),
        ("ck", 0, 23), ("dec", 65), ("dec", 77)]
ALIAS = (5, 6, 1)          # segs 5 and 6 share physical block 0


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_fused_matches_two_kernel_paths(dtype, tol):
    """One fused launch == the decode-flat + prefill-chunk pair it
    replaces, on the SAME pool."""
    C, BS = 32, 16
    q, kp, vp, bt, ctx, seg, tags, full = _mixed_case(
        SEGS, C, 8, 2, 64, BS, dtype, alias=ALIAS)
    dec = np.asarray([i for i, s in enumerate(SEGS)
                      if s[0] == "dec" and s[1] > 0])
    ck = np.asarray([i for i, s in enumerate(SEGS) if s[0] == "ck"])
    lens = jnp.asarray([SEGS[i][1] for i in dec], jnp.int32)
    ref_dec = paged_decode_attention_flat(
        q[dec, 0], kp, vp, bt[dec, :], lens, interpret=True)
    ref_ck = paged_prefill_attention(
        q[ck, :], kp, vp, bt[ck, :], ctx[ck], seg[ck], interpret=True)
    out = np.asarray(paged_mixed_attention(
        q, kp, vp, bt, ctx, seg, tags, interpret=True), np.float32)
    for r, i in enumerate(dec):
        np.testing.assert_allclose(
            out[i, 0], np.asarray(ref_dec, np.float32)[r],
            atol=tol, rtol=tol)
    for r, i in enumerate(ck):
        cl = int(seg[i])
        np.testing.assert_allclose(
            out[i, :cl], np.asarray(ref_ck, np.float32)[r, :cl],
            atol=tol, rtol=tol)


def _segment_oracle(q, full, segs):
    """Plain float64 attention of each live segment's query rows over its
    own contiguous K/V: row r of a segment with context ``ctx`` sits at
    position ctx + r and sees keys 0..ctx + r. {segment: [rows, H, Dh]}."""
    q = np.asarray(q, np.float64)
    H, Dh = q.shape[2], q.shape[3]
    out = {}
    for i, s in enumerate(segs):
        ctx, n = (s[1] - 1, 1) if s[0] == "dec" else (s[1], s[2])
        if ctx + n == 0:
            continue                             # dead segment: no rows
        k, v = (np.asarray(a, np.float64) for a in full[i])
        G = H // k.shape[1]
        rows = np.zeros((n, H, Dh))
        for r in range(n):
            for hq in range(H):
                sc = k[:ctx + r + 1, hq // G] @ q[i, r, hq] / math.sqrt(Dh)
                p = np.exp(sc - sc.max())
                rows[r, hq] = p @ v[:ctx + r + 1, hq // G] / p.sum()
        out[i] = rows
    return out


# the device-counted grid: mixed tags with a dead segment and a shared
# prefix block; one real item among dead slots; no item at all; and every
# segment filling its table, so the count equals the work-list capacity
COUNT_CASES = {
    "mixed": (SEGS, ALIAS, None),
    "single_item": ([("dec", 0), ("dec", 5), ("dec", 0)], None, None),
    "empty": ([("dec", 0), ("ck", 0, 0), ("dec", 0)], None, None),
    "at_capacity": ([("ck", 32, 16), ("dec", 48), ("ck", 40, 8)], None, 3),
}


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
@pytest.mark.parametrize("case", sorted(COUNT_CASES))
def test_fused_device_count_matches_oracle(case, kv_dtype):
    """The kernel steps through Σ_s ceil((ctx_s + seg_s)/BS) items, counted
    on the device, and every live row matches the plain attention of its
    segment; int8 blocks within the quantized-KV contract (abs <= 0.05).
    An empty call runs its one skipped step and writes zeros into the
    last (dead) segment's row only."""
    segs, alias, nbt = COUNT_CASES[case]
    C, BS = 32, 16
    q, kp, vp, bt, ctx, seg, tags, full = _mixed_case(
        segs, C, 8, 2, 64, BS, jnp.float32, alias=alias, nbt=nbt)
    count = sum(-(-int(c + n) // BS) for c, n in zip(ctx, seg))
    B, NBT = bt.shape
    assert (count == B * NBT) == (case == "at_capacity")
    scales = ()
    if kv_dtype == "int8":
        (kp, ks), (vp, vs) = quantize_kv(kp), quantize_kv(vp)
        scales = (ks, vs)
    out = np.asarray(paged_mixed_attention(
        q, kp, vp, bt, ctx, seg, tags, *scales, interpret=True), np.float64)
    tol = 3e-5 if kv_dtype == "f32" else 0.05
    want = _segment_oracle(q, full, segs)
    assert (len(want) == 0) == (case == "empty")
    for i, rows in want.items():
        np.testing.assert_allclose(out[i, :len(rows)], rows, atol=tol,
                                   rtol=tol)
    if case == "empty":
        assert not np.any(out[-1])


def test_fused_one_program_across_counts():
    """Two calls of the same shapes whose real item counts differ share
    ONE compiled program: the count is a traced grid bound, not a static
    argument."""
    C, BS = 8, 16                      # shapes no other test uses
    n0 = paged_mixed_attention._cache_size()
    counts = []
    for segs in ([("dec", 40), ("ck", 16, 8), ("dec", 3)],
                 [("dec", 7), ("ck", 0, 5), ("dec", 0)]):
        q, kp, vp, bt, ctx, seg, tags, full = _mixed_case(
            segs, C, 6, 3, 32, BS, jnp.float32, nbt=4)
        # one pool size for both calls
        pad = ((0, 16 - kp.shape[0]), (0, 0), (0, 0), (0, 0))
        kp, vp = jnp.pad(kp, pad), jnp.pad(vp, pad)
        counts.append(sum(-(-int(c + n) // BS) for c, n in zip(ctx, seg)))
        out = np.asarray(paged_mixed_attention(
            q, kp, vp, bt, ctx, seg, tags, interpret=True), np.float64)
        for i, rows in _segment_oracle(q, full, segs).items():
            np.testing.assert_allclose(out[i, :len(rows)], rows, atol=3e-5,
                                       rtol=3e-5)
    assert counts[0] != counts[1]
    assert paged_mixed_attention._cache_size() == n0 + 1


def test_fused_decode_rows_match_dense_oracle():
    """Anchor beyond kernel-vs-kernel: fused decode rows reproduce the
    dense attention oracle over each segment's contiguous cache."""
    C, BS, H, Hkv, Dh = 32, 16, 8, 2, 64
    q, kp, vp, bt, ctx, seg, tags, full = _mixed_case(
        SEGS, C, H, Hkv, Dh, BS, jnp.float32, alias=ALIAS)
    out = np.asarray(paged_mixed_attention(
        q, kp, vp, bt, ctx, seg, tags, interpret=True), np.float32)
    dec = np.asarray([i for i, s in enumerate(SEGS)
                      if s[0] == "dec" and s[1] > 0])
    kd = jnp.asarray(np.stack([full[i][0] for i in dec]))
    vd = jnp.asarray(np.stack([full[i][1] for i in dec]))
    ref = decode_attention_ref(q[dec, 0], kd, vd,
                               jnp.asarray([SEGS[i][1] for i in dec],
                                           jnp.int32))
    np.testing.assert_allclose(out[dec, 0], np.asarray(ref, np.float32),
                               atol=3e-5, rtol=3e-5)


def test_fused_int8_bounded_error():
    """Contract (DESIGN.md §Quantized KV blocks): per-row symmetric int8
    with per-(block, position, kv-head) scales keeps every live output row
    within cos >= 0.999 / abs <= 0.05 of the full-precision kernel."""
    C, BS = 32, 16
    q, kp, vp, bt, ctx, seg, tags, _ = _mixed_case(
        SEGS, C, 8, 2, 64, BS, jnp.float32, alias=ALIAS)
    ref = np.asarray(paged_mixed_attention(
        q, kp, vp, bt, ctx, seg, tags, interpret=True), np.float32)
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    out = np.asarray(paged_mixed_attention(
        q, kq, vq, bt, ctx, seg, tags, ks, vs, interpret=True), np.float32)
    for i, s in enumerate(SEGS):
        rows = range(1 if s[0] == "dec" else s[2])
        if s[0] == "dec" and s[1] == 0:
            continue                             # dead slot: garbage row
        for r in rows:
            a, b = out[i, r].ravel(), ref[i, r].ravel()
            cos = float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b),
                                    1e-12))
            assert cos >= 0.999, (i, r, cos)
            assert float(np.abs(a - b).max()) <= 0.05, (i, r)


def test_quantize_roundtrip_and_garbage_blocks():
    """quantize -> dequantize is a contraction (error < one quant step per
    element); zero-initialized garbage blocks carry zero scales and
    dequantize to EXACT zeros, keeping the sentinel discipline intact."""
    x = jnp.asarray(RNG.normal(0, 1, (4, 16, 2, 64)), jnp.float32)
    piece = KVCache(x, -x)
    back = dequantize_piece(quantize_piece(piece), jnp.float32)
    step = np.abs(np.asarray(x)).max(-1, keepdims=True) / 127.0
    assert np.all(np.abs(np.asarray(back.k - x)) <= step * 0.5 + 1e-7)
    zero = KVCache(jnp.zeros_like(x), jnp.zeros_like(x))
    zq = quantize_piece(zero)
    assert float(jnp.abs(dequantize_piece(zq, jnp.float32).k).max()) == 0.0


# --------------------------------------------------------------------------
# Engine: greedy parity + the one-call / one-sync contracts
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _drain(eng, reqs, max_iters=400):
    for r in reqs:
        eng.submit(r)
    out = []
    for _ in range(max_iters):
        out += eng.step()
        if len(out) == len(reqs):
            return out
    raise AssertionError("engine did not drain")


@pytest.mark.parametrize("kv_dtype,exact", [("bf16", True), ("int8", False)])
def test_fused_engine_greedy_parity_vs_dense(setup, rng, kv_dtype, exact):
    """Full-precision fused engine emits bit-identical greedy tokens to
    the dense baseline (fusing reshapes launches, never values); int8
    drifts boundedly — same stream lengths, documented accuracy contract
    covered at the kernel level."""
    cfg, model, params = setup
    prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in (5, 23, 12)]
    outs = {}
    for backend, kvd in (("dense", "bf16"), ("fused", kv_dtype)):
        eng = Engine(0, model, params, max_slots=3, max_seq=64,
                     attn_backend=backend, kv_dtype=kvd,
                     prefill_token_budget=8)
        assert eng.fused_mixed == (backend == "fused")
        reqs = [ServeRequest(i, p.copy(), 8) for i, p in enumerate(prompts)]
        _drain(eng, reqs)
        outs[backend] = [list(r.generated) for r in reqs]
    if exact:
        assert outs["fused"] == outs["dense"]
    else:
        assert [len(t) for t in outs["fused"]] == \
            [len(t) for t in outs["dense"]]


def test_fused_mixed_step_one_attn_call_one_sync(setup, rng, monkeypatch):
    """Acceptance: while a long prompt chunks beside a live decode batch,
    EVERY fused mixed step makes exactly ONE attention-bearing device
    call (attn_call shim) and exactly ONE device->host sync (d2h shim);
    the separate-kernel reference makes two calls on the same trace."""
    cfg, model, params = setup
    d2h_calls = []
    real = engine_mod.d2h
    monkeypatch.setattr(engine_mod, "d2h",
                        lambda x: d2h_calls.append(1) or real(x))
    launches = []

    class Span:                 # the profiler span, recording launch kinds
        def __init__(self, name, **stats):
            if name == "engine.launch":
                launches.append(stats["kind"])

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **stats):
            pass

        @staticmethod
        def is_enabled():
            return True

    monkeypatch.setattr(engine_mod, "_span", Span)

    def trace(backend):
        eng = Engine(0, model, params, max_slots=4, max_seq=128,
                     attn_backend=backend, prefill_token_budget=8)
        short = [ServeRequest(i, rng.integers(0, cfg.vocab_size, p)
                              .astype(np.int32), 12)
                 for i, p in enumerate((5, 11))]
        for r in short:
            eng.submit(r)
        while any(r.prefilling or r.state.name == "WAITING" for r in short):
            eng.step()
        long_req = ServeRequest(9, rng.integers(0, cfg.vocab_size, 24)
                                .astype(np.int32), 2)
        eng.submit(long_req)
        attn, sync = [], []
        launches.clear()
        while long_req.prefilling or long_req.first_token_step is None:
            d2h_calls.clear()
            c0 = engine_mod.ATTN_CALLS
            eng.step()
            attn.append(engine_mod.ATTN_CALLS - c0)
            sync.append(len(d2h_calls))
        return attn, sync, list(launches)

    attn, sync, kinds = trace("fused")
    assert attn and max(attn) == 1, attn
    assert all(s == 1 for s in sync), sync
    assert "mixed" in kinds                      # mixed steps went fused
    attn_sep, sync_sep, _ = trace("flat")
    assert 2 in attn_sep, attn_sep               # the two-launch baseline
    assert all(s == 1 for s in sync_sep), sync_sep


def _record_mixed_launches(monkeypatch):
    """Stand in for the engine's profiler span; returns the list that
    collects each mixed launch's stats."""
    stats = []

    class Span:
        def __init__(self, name, **kw):
            self.launch = name == "engine.launch" and kw["kind"] == "mixed"

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            if self.launch:
                stats.append(kw)

        @staticmethod
        def is_enabled():
            return True

    monkeypatch.setattr(engine_mod, "_span", Span)
    return stats


def test_fused_engine_one_mixed_program_across_counts(setup, rng,
                                                      monkeypatch):
    """Mixed steps whose work counts differ, at one table width and one
    chunk shape, run ONE compiled mixed program; the launched items are
    the real ones (no padding), over a work list of (slots + chunks) x
    table width."""
    cfg, model, params = setup
    stats = _record_mixed_launches(monkeypatch)
    # max_seq = one block: every table is one column wide; 4-token prompts
    # under a 4-token budget: one 4-row chunk a step, beside a decode
    # batch that fills up and drains
    eng = Engine(0, model, params, max_slots=4, max_seq=16, block_size=16,
                 attn_backend="fused", prefill_token_budget=4)
    assert eng.fused_mixed
    reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size, 4)
                         .astype(np.int32), 6) for i in range(7)]
    for r in reqs:
        eng.submit(r)
    counts, done = set(), []
    while len(done) < len(reqs):
        m0, real0 = eng.mixed_steps, eng.real_work_items
        done += eng.step()
        if eng.mixed_steps > m0:
            counts.add(eng.real_work_items - real0)
    assert len(counts) >= 3, counts
    assert eng._mixed._cache_size() == 1
    assert eng.work_items == eng.real_work_items > 0
    assert stats and all(s["items"] == s["real_items"] <= s["capacity"] == 5
                         for s in stats), stats


@pytest.mark.parametrize("limit", ["chip", "two_chunks", "no_room"])
def test_fused_engine_chunks_fit_work_list_limit(setup, rng, monkeypatch,
                                                 limit):
    """The fused kernel holds (decode rows + chunks) x table width work
    items in SMEM. Under the chip's limit a step plans a chunk for every
    slot beside the decode rows (B = 2·max_slots); under a limit that
    fits two chunks it plans two a step and serves the same greedy
    tokens; where no chunk fits beside the slots, the engine refuses to
    build."""
    cfg, model, params = setup
    # budget 16 -> chunk tables up to blocks_for(32 + 16) = 3 columns
    kw = dict(max_slots=4, max_seq=32, block_size=16, attn_backend="fused",
              prefill_token_budget=16)
    prompts = [rng.integers(0, cfg.vocab_size, 3).astype(np.int32)
               for _ in range(4)]
    want = None
    if limit != "chip":
        want = _drain(Engine(0, model, params, **kw),
                      [ServeRequest(i, p, 4) for i, p in enumerate(prompts)])
        cap = {"two_chunks": (4 + 2) * 3, "no_room": 4 * 3}[limit]
        monkeypatch.setattr(engine_mod, "MAX_WORK_ITEMS", cap)
        if limit == "no_room":
            with pytest.raises(ValueError, match="no room for a prefill"):
                Engine(0, model, params, **kw)
            return
    stats = _record_mixed_launches(monkeypatch)
    eng = Engine(0, model, params, **kw)
    plan, chunks = eng._plan_chunks, []

    def counted_plan():
        rejected, p = plan()
        chunks.append(len(p))
        return rejected, p

    eng._plan_chunks = counted_plan
    got = _drain(eng, [ServeRequest(i, p, 4) for i, p in enumerate(prompts)])
    chunks = [n for n in chunks if n]
    if limit == "chip":
        assert chunks == [4]                     # B = 2·max_slots
    else:
        assert chunks == [2, 2]
        assert all(s["capacity"] <= engine_mod.MAX_WORK_ITEMS
                   for s in stats), stats
        assert ({r.req_id: r.generated for r in got}
                == {r.req_id: r.generated for r in want})


# --------------------------------------------------------------------------
# Backend resolution + the split-pow2 cost mirror
# --------------------------------------------------------------------------
def test_resolve_backend_fused_auto_on_tpu_dense_elsewhere():
    choice, interpret = resolve_paged_backend()
    on_tpu = jax.default_backend() == "tpu"
    assert choice == ("fused" if on_tpu else "dense")
    choice, interpret = resolve_paged_backend("fused")
    assert choice == "fused" and interpret == (not on_tpu)


def test_cost_fused_split_buckets_and_launch_saving():
    """The fused mirror prices the real decode and chunk items only (the
    kernel counts them on the device), so against the separate kernels it
    saves the extra launch PLUS both halves' pow2 padding tails, exactly,
    for any shape."""
    BS = 16
    spec = AttnSpec(8, 2, 64, block_s=BS)
    for lens, cks in (([7, 32, 152, 700], [(64, 256)]),   # unlucky bucket
                      ([16 * 9], [(8 * 16, 0)]),
                      ([4, 512, 1], [(32, 100), (17, 48)])):
        dec = sum(blocks_for(n, BS) for n in lens)
        ck = sum(blocks_for(c + x, BS) for c, x in cks)
        padding = pow2_bucket(dec) - dec + pow2_bucket(ck) - ck
        t_fused = mixed_iter_time_s(cks, lens, spec, decode_backend="fused")
        t_sep = mixed_iter_time_s(cks, lens, spec, decode_backend="flat")
        assert t_fused < t_sep
        np.testing.assert_allclose(
            t_sep - t_fused,
            LAUNCH_OVERHEAD_S + spec.num_kv_heads * padding * SKIP_OVERHEAD_S,
            rtol=1e-9)
    # no chunks -> no launch to save: fused == flat less the decode tail
    lens = [64, 256]                       # 20 blocks -> pow2 32
    np.testing.assert_allclose(
        mixed_iter_time_s([], lens, spec, decode_backend="flat")
        - mixed_iter_time_s([], lens, spec, decode_backend="fused"),
        spec.num_kv_heads * 12 * SKIP_OVERHEAD_S, rtol=1e-9)


def test_cost_kv_bytes_per_elem():
    assert kv_bytes_per_elem("bf16", 128) == 2.0
    assert kv_bytes_per_elem("int8", 128) == pytest.approx(1.03125)
    # the residency bound: 2*Dh/(Dh+4) ~ 1.94x at Dh=128, 1.88x at Dh=64
    for dh, bound in ((128, 1.939), (64, 1.88)):
        assert 2.0 / kv_bytes_per_elem("int8", dh) >= bound
