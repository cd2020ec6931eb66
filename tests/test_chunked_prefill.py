"""Chunked paged prefill + mixed-iteration scheduling (DESIGN.md §Chunked
prefill): the flat work-list prefill kernel vs. a dense oracle,
chunk-by-chunk vs. whole-prompt parity on logits and pool contents (mock
and real model), mixed-iteration decode parity against the monolithic
(PR 3) loop, decode-stall bounds while a long prompt chunks, migration
round-trip of a half-prefilled request, and the analytic cost mirrors."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serving.engine as engine_mod
from repro.configs import get_config
from repro.kernels.cost import (AttnSpec, mixed_iter_time_s, pow2_bucket,
                                prefill_chunk_blocks, prefill_chunk_flops)
from repro.kernels.prefill_attention import (paged_prefill_attention,
                                             prefill_attention)
from repro.kernels.ref import prefill_attention_ref
from repro.models import build_model
from repro.models.model import Model
from repro.serving.block_pool import blocks_for
from repro.serving.engine import Engine
from repro.serving.request import ServeRequest, State

RNG = np.random.default_rng(11)


# --------------------------------------------------------------------------
# Satellite: prefill_attention no longer requires T % block == 0
# --------------------------------------------------------------------------
@pytest.mark.parametrize("T,bq,bk", [(100, 32, 32), (37, 64, 32), (1, 64, 64)])
def test_prefill_attention_pads_internally(T, bq, bk):
    B, H, Hkv, Dh = 2, 4, 2, 64
    q = jnp.asarray(RNG.normal(0, 1, (B, T, H, Dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (B, T, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (B, T, Hkv, Dh)), jnp.float32)
    lens = jnp.asarray([T, max(T // 3, 1)], jnp.int32)
    ref = prefill_attention_ref(q, k, v, lens)
    out = prefill_attention(q, k, v, lens, block_q=bq, block_k=bk,
                            interpret=True)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


# --------------------------------------------------------------------------
# Kernel: chunked paged prefill vs. dense oracle
# --------------------------------------------------------------------------
def _chunk_case(chunks, C, H, Hkv, Dh, BS, dtype):
    """Per chunk (ctx, clen): contiguous KV for positions [0, ctx+clen)
    scattered into a shuffled physical pool; the reference attends
    causally over it."""
    Bc = len(chunks)
    NBT = max(-(-(ctx + C) // BS) for ctx, _ in chunks) + 1
    NB = Bc * NBT + 2
    perm = RNG.permutation(NB)
    k_pool = np.zeros((NB, Hkv, BS, Dh), np.float32)  # head-major pool
    v_pool = np.zeros_like(k_pool)
    bt = np.full((Bc, NBT), NB - 1, np.int32)
    full = []
    pi = 0
    for c, (ctx, clen) in enumerate(chunks):
        kk = RNG.normal(0, 1, (NBT * BS, Hkv, Dh)).astype(np.float32)
        vv = RNG.normal(0, 1, (NBT * BS, Hkv, Dh)).astype(np.float32)
        full.append((kk, vv))
        for j in range(-(-(ctx + clen) // BS)):
            pb = int(perm[pi]); pi += 1
            bt[c, j] = pb
            k_pool[pb] = kk[j * BS:(j + 1) * BS].swapaxes(0, 1)
            v_pool[pb] = vv[j * BS:(j + 1) * BS].swapaxes(0, 1)
    q = RNG.normal(0, 1, (Bc, C, H, Dh)).astype(np.float32)
    ref = np.zeros((Bc, C, H, Dh), np.float32)
    for c, (ctx, clen) in enumerate(chunks):
        kk, vv = full[c]
        for i in range(clen):
            qi = q[c, i].reshape(Hkv, H // Hkv, Dh)
            n = ctx + i + 1                     # causal: kv pos <= ctx + i
            s = np.einsum("hgd,shd->hgs", qi, kk[:n]) / np.sqrt(Dh)
            w = np.exp(s - s.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            ref[c, i] = np.einsum("hgs,shd->hgd", w, vv[:n]).reshape(H, Dh)
    to = lambda a: jnp.asarray(a, dtype)
    return (to(q), to(k_pool), to(v_pool), jnp.asarray(bt),
            jnp.asarray([c for c, _ in chunks], jnp.int32),
            jnp.asarray([l for _, l in chunks], jnp.int32), ref)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 1e-2)])
def test_paged_prefill_kernel_matches_ref(dtype, tol):
    """Mixed batch: a fresh chunk (ctx 0), a resumed mid-prompt chunk, a
    single-token chunk dragging a long context, and an exact
    block-boundary case — each attends to its own context only."""
    chunks = [(0, 20), (48, 32), (167, 1), (64, 32)]      # (ctx, clen)
    C, BS = 32, 16
    q, kp, vp, bt, ctx, clen, ref = _chunk_case(chunks, C, 8, 2, 64, BS,
                                                dtype)
    total = sum(-(-(a + b) // BS) for a, b in chunks)
    for W in (total, pow2_bucket(total), None):
        out = paged_prefill_attention(q, kp, vp, bt, ctx, clen,
                                      num_work=W, interpret=True)
        out = np.asarray(out, np.float32)
        for c, (_, cl) in enumerate(chunks):
            np.testing.assert_allclose(out[c, :cl], ref[c, :cl],
                                       atol=tol, rtol=tol)


# --------------------------------------------------------------------------
# Real model: chunk-by-chunk == whole-prompt (logits AND pool contents)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_real_chunked_matches_whole_prompt(setup):
    """Acceptance: running a prompt chunk-by-chunk through the paged pool
    reproduces the whole-prompt prefill's next-token logits and every
    cache row (and the greedy first token exactly)."""
    cfg, model, params = setup
    T, BS = 29, 8
    toks = RNG.integers(0, cfg.vocab_size, (1, T)).astype(np.int32)
    ref_logits, ref_piece = model.prefill(
        params, {"tokens": jnp.asarray(toks)}, cache_len=T)

    NB = 16
    pool = model.init_paged_cache(NB, BS)
    ids = [5, 2, 9, 11]
    garbage = NB - 1
    fn = jax.jit(model.prefill_chunk)
    ctx = 0
    for clen in (10, 8, 11):                    # uneven chunk plan
        C = 16
        t = np.zeros((1, C), np.int32)
        t[0, :clen] = toks[0, ctx:ctx + clen]
        bt = np.full((1, blocks_for(ctx + C, BS)), garbage, np.int32)
        nreal = blocks_for(ctx + clen, BS)
        bt[0, :nreal] = ids[:nreal]
        logits, pool = fn(params, pool, jnp.asarray(t), jnp.asarray(bt),
                          jnp.int32(ctx), jnp.int32(clen))
        ctx += clen

    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               atol=2e-5, rtol=2e-5)
    assert int(jnp.argmax(logits[0])) == int(jnp.argmax(ref_logits[0]))
    for pool_l, piece_l in zip((pool.k, pool.v), (ref_piece.k, ref_piece.v)):
        # head-major, lane-padded blocks -> token-major rows
        got = np.asarray(pool_l, np.float32)[:, ids].swapaxes(2, 3)
        got = got.reshape(got.shape[0], -1, *got.shape[3:])[
            :, :T, :, :cfg.head_dim]
        np.testing.assert_allclose(got, np.asarray(piece_l, np.float32)[:, 0],
                                   atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------
# Mock model (plumbing parity): token_{t+1} = f(token_t, pos_t)
# --------------------------------------------------------------------------
MOCK_VOCAB = 97


def _mock_next(tok, pos):
    return (31 * tok + 7 * pos + 3) % MOCK_VOCAB


def make_chunk_mock_model():
    """The test_hotloop mock plus a prefill_chunk — the engine's chunked
    scheduler sees a model whose first token depends only on the LAST
    prompt token and position, so chunked and whole-prompt prefill must
    emit identical greedy streams."""
    cfg = get_config("smollm-360m").reduced()

    def _logits(tok, pos):
        return jax.nn.one_hot(_mock_next(tok, pos), MOCK_VOCAB)

    def prefill(params, batch, cache_len=None):
        tokens = batch["tokens"]
        T = tokens.shape[1]
        piece = {"kv": jnp.zeros((1, 1, T, 1, 1), jnp.float32)}
        return _logits(tokens[:, -1], jnp.full((1,), T - 1)), piece

    def prefill_bucketed(params, batch, true_len):
        tokens = batch["tokens"]
        last = jnp.take_along_axis(tokens, true_len[None, None] - 1,
                                   axis=1)[:, 0]
        piece = {"kv": jnp.zeros((1, 1, tokens.shape[1], 1, 1), jnp.float32)}
        return _logits(last, true_len[None] - 1), piece

    def prefill_chunk(params, pool, tokens, block_tables, ctx_len,
                      chunk_len, **kw):
        B = tokens.shape[0]
        clen = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32)
                                .reshape(-1), (B,))
        ctx = jnp.broadcast_to(jnp.asarray(ctx_len, jnp.int32)
                               .reshape(-1), (B,))
        last = jnp.take_along_axis(tokens, (clen - 1)[:, None],
                                   axis=1)[:, 0]
        return _logits(last, ctx + clen - 1), pool

    def decode_step_paged(params, pool, token, block_tables, pos, **kw):
        return _logits(token, pos), pool

    def decode_step(params, cache, token, pos, **kw):
        return _logits(token, pos), cache

    def init_paged_cache(num_blocks, block_size):
        return {"kv": jnp.zeros((1, num_blocks, 1, block_size, 1),
                                jnp.float32)}

    def init_cache(batch, seq):
        return {"kv": jnp.zeros((1, batch, seq, 1, 1), jnp.float32)}

    return Model(cfg, lambda rng: {}, loss=None, prefill=prefill,
                 decode_step=decode_step, init_cache=init_cache,
                 init_paged_cache=init_paged_cache,
                 decode_step_paged=decode_step_paged,
                 prefill_bucketed=prefill_bucketed,
                 prefill_chunk=prefill_chunk)


def _drain(eng, reqs, burst=1, max_iters=500):
    for r in reqs:
        eng.submit(r)
    for _ in range(max_iters):
        eng.step(burst)
        if all(r.state is State.FINISHED for r in reqs):
            return
    raise AssertionError("engine did not drain")


def _mock_reqs(plens, news, seed=1):
    r = np.random.default_rng(seed)
    return [ServeRequest(i, r.integers(0, MOCK_VOCAB, p).astype(np.int32),
                         m)
            for i, (p, m) in enumerate(zip(plens, news))]


@pytest.mark.parametrize("mode", ["host", "device", "device_burst"])
def test_mock_mixed_iteration_matches_monolithic(mode):
    """Acceptance (mixed-iteration decode bit-parity vs. the PR 3 loop):
    the chunked scheduler — prompts larger than the budget, max_new=1
    requests, slot reuse — emits exactly the monolithic engine's greedy
    tokens on every path (host, device, fused burst)."""
    plens = [3, 41, 9, 17, 26]
    news = [7, 5, 1, 11, 4]
    model = make_chunk_mock_model()
    device = mode != "host"
    burst = 8 if mode == "device_burst" else 1
    base = Engine(0, model, {}, max_slots=3, max_seq=64,
                  device_resident=device, chunked_prefill=False)
    reqs_a = _mock_reqs(plens, news)
    _drain(base, reqs_a, burst)
    chunked = Engine(0, model, {}, max_slots=3, max_seq=64,
                     device_resident=device, prefill_token_budget=8)
    reqs_b = _mock_reqs(plens, news)
    _drain(chunked, reqs_b, burst)
    assert [r.generated for r in reqs_a] == [r.generated for r in reqs_b]
    assert chunked.free_tokens() >= 0 and chunked.queued_tokens() == 0


def test_mock_no_decode_stall_while_long_prompt_chunks():
    """Acceptance: a long prompt arriving into a busy decode batch never
    opens an inter-token gap — every running decode request gains exactly
    one token per mixed iteration while the prompt chunks, and the
    prompt's first token lands after ceil(T/budget) iterations."""
    model = make_chunk_mock_model()
    budget = 8
    eng = Engine(0, model, {}, max_slots=4, max_seq=256,
                 prefill_token_budget=budget)
    decode = _mock_reqs([4, 6, 5], [120, 120, 120])
    for r in decode:
        eng.submit(r)
    for _ in range(4):                           # decode batch fully live
        eng.step()
    assert all(not r.prefilling for r in decode)
    T = 64
    long = ServeRequest(9, RNG.integers(0, MOCK_VOCAB, T).astype(np.int32),
                        4)
    eng.submit(long)
    steps = 0
    while long.prefilling:
        before = [len(r.generated) for r in decode]
        eng.step()
        steps += 1
        after = [len(r.generated) for r in decode]
        assert [a - b for a, b in zip(after, before)] == [1, 1, 1], \
            "a decode request stalled during chunked prefill"
    assert steps == -(-T // budget)              # one budget per iteration
    # the final-chunk step emits the first token AND decodes once (the
    # completed request joins the decode batch the same step, like PR 3
    # whole-prompt admission did)
    assert len(long.generated) == 2
    assert long.first_token_step == eng.steps
    # monolithic baseline for contrast: whole-prompt admission in 1 step
    mono = Engine(0, model, {}, max_slots=4, max_seq=256,
                  chunked_prefill=False)
    ml = ServeRequest(9, long.prompt.copy(), 4)
    mono.submit(ml)
    mono.step()
    assert ml.ctx_done == T                      # one shot, one iteration


def test_mock_chunked_one_device_sync_per_step(monkeypatch):
    """The mixed iteration keeps the PR 3 contract: chunk calls, final-
    chunk first tokens, and the decode burst all ride AT MOST one d2h per
    step — exactly one whenever a token reaches the host, zero on
    pure-chunk steps (nothing to transfer at all)."""
    model = make_chunk_mock_model()
    calls = []
    real = engine_mod.d2h
    monkeypatch.setattr(engine_mod, "d2h",
                        lambda x: calls.append(1) or real(x))
    for burst in (1, 8):
        eng = Engine(0, model, {}, max_slots=3, max_seq=64,
                     prefill_token_budget=8)
        reqs = _mock_reqs([20, 3, 11], [6, 6, 6])
        for r in reqs:
            eng.submit(r)
        saw_zero_sync_chunk_step = False
        while any(r.state is not State.FINISHED for r in reqs):
            before = sum(len(r.generated) for r in reqs)
            calls.clear()
            eng.step(burst)
            emitted = sum(len(r.generated) for r in reqs) - before
            assert len(calls) <= 1
            assert len(calls) == 1 or emitted == 0
            saw_zero_sync_chunk_step |= (len(calls) == 0)
        assert saw_zero_sync_chunk_step, \
            "expected at least one pure-chunk step with zero transfers"


def test_mock_queued_tokens_counts_unprefilled_only():
    model = make_chunk_mock_model()
    eng = Engine(0, model, {}, max_slots=2, max_seq=256,
                 prefill_token_budget=8)
    eng.submit(ServeRequest(0, np.ones(30, np.int32), 4))
    eng.submit(ServeRequest(1, np.ones(12, np.int32), 4))
    assert eng.queued_tokens() == 42
    eng.step()     # 8 tokens of req 0 chunked; req 1 still fully queued
    assert eng.queued_tokens() == 22 + 12
    assert eng.used_tokens() == blocks_for(8, eng.block_size) \
        * eng.block_size
    eng.step()
    assert eng.queued_tokens() == 14 + 12


# --------------------------------------------------------------------------
# Real model: engine-level chunked parity + migration of a partial prompt
# --------------------------------------------------------------------------
def test_real_engine_chunked_parity_all_paths(setup):
    """Greedy streams are identical across monolithic/chunked ×
    host/device — chunked prefill changes latency shape, never tokens."""
    cfg, model, params = setup
    prompts = [RNG.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in (5, 23, 12)]
    outs = {}
    for name, kw in {
        "mono": dict(chunked_prefill=False),
        "chunk_host": dict(device_resident=False, prefill_token_budget=8),
        "chunk_dev": dict(device_resident=True, prefill_token_budget=8),
    }.items():
        eng = Engine(0, model, params, max_slots=3, max_seq=64, **kw)
        reqs = [ServeRequest(i, p.copy(), 8) for i, p in enumerate(prompts)]
        _drain(eng, reqs)
        outs[name] = [list(r.generated) for r in reqs]
    assert outs["mono"] == outs["chunk_host"] == outs["chunk_dev"]


@pytest.mark.parametrize("backend", ["grid", "flat"])
def test_real_chunked_kernel_backend_matches_dense(setup, backend):
    """The chunked path through the Pallas prefill kernel (interpret mode
    off-TPU) agrees with the dense-gather fallback."""
    cfg, model, params = setup
    prompts = [RNG.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in (21, 6)]
    outs = []
    for be in (backend, "dense"):
        eng = Engine(0, model, params, max_slots=2, max_seq=64,
                     attn_backend=be, prefill_token_budget=8)
        reqs = [ServeRequest(i, p.copy(), 5) for i, p in enumerate(prompts)]
        _drain(eng, reqs)
        outs.append([list(r.generated) for r in reqs])
    assert outs[0] == outs[1]


def test_half_prefilled_migration_roundtrip(setup):
    """Acceptance: a request exported mid-prefill ships exactly its
    ctx_done written rows, the receiver resumes chunking, and the final
    greedy stream equals an unmigrated run."""
    cfg, model, params = setup
    mk = lambda i: Engine(i, model, params, max_slots=2, max_seq=64,
                          prefill_token_budget=8)
    src, dst, ref_eng = mk(0), mk(1), mk(2)
    prompt = RNG.integers(0, cfg.vocab_size, 30).astype(np.int32)
    r = ServeRequest(0, prompt.copy(), 6)
    ref = ServeRequest(9, prompt.copy(), 6)
    src.submit(r)
    ref_eng.submit(ref)
    src.step()
    src.step()
    assert r.ctx_done == 16 and r.prefilling
    req, piece, nbytes = src.export_slot(r.slot)
    assert jax.tree.leaves(piece)[0].shape[2] == 16, \
        "partial export must ship exactly the written rows"
    assert dst.import_request(req, piece)
    src.evict_slot(0)
    assert src.used_tokens() == 0 and src.queued_tokens() == 0
    while r.state is not State.FINISHED:
        dst.step()
    while ref.state is not State.FINISHED:
        ref_eng.step()
    assert r.generated == ref.generated
    assert r.tokens_by_engine[1] == len(r.generated)


def test_partial_import_refused_without_chunking(setup):
    cfg, model, params = setup
    src = Engine(0, model, params, max_slots=2, max_seq=64,
                 prefill_token_budget=8)
    mono = Engine(1, model, params, max_slots=2, max_seq=64,
                  chunked_prefill=False)
    r = ServeRequest(0, RNG.integers(0, cfg.vocab_size, 30)
                     .astype(np.int32), 6)
    src.submit(r)
    src.step()
    req, piece, _ = src.export_slot(r.slot)
    assert req.prefilling
    assert not mono.import_request(req, piece)


# --------------------------------------------------------------------------
# Cost-model mirrors
# --------------------------------------------------------------------------
def test_prefill_chunk_cost_mirrors():
    spec = AttnSpec(num_q_heads=32, num_kv_heads=8, head_dim=128)
    # grid work: chunk × context blocks
    assert prefill_chunk_blocks(256, 4096, 512) == math.ceil(4352 / 512)
    # summing a prompt's chunks recovers the causal whole-prompt count
    I, C = 8192, 256
    whole = prefill_chunk_flops(I, 0, spec)
    chunked = sum(prefill_chunk_flops(C, i * C, spec) for i in range(I // C))
    assert abs(chunked - whole) / whole < 0.05
    # a mixed iteration costs ~one chunk, not one monolithic prompt
    mixed = mixed_iter_time_s([(256, 16384)], [1024] * 8, spec)
    mono = prefill_chunk_flops(32768, 0, spec) / 197e12
    assert mixed < mono / 20


def test_sim_mixed_iterations_bound_decode_gaps():
    """Sim mirror of the engine acceptance: with the chunked scheduler a
    32K prompt landing on a busy instance never stretches an iteration
    beyond ~one budget's work; monolithic prefill stalls the whole batch
    for the full prompt."""
    from repro.sim.costmodel import profile_from_config
    from repro.sim.events import EventQueue
    from repro.sim.instance import Instance, SimRequest
    from repro.sim.workload import Request

    prof = profile_from_config(get_config("llama3.2-3b"))
    gaps = {}
    for name, budget in (("chunked", 2048), ("mono", None)):
        ev = EventQueue()
        inst = Instance(0, prof, 200_000, ev, prefill_budget=budget)
        for i in range(4):
            inst.enqueue(SimRequest(req=Request(i, 0.0, 64, 400),
                                    length=64), 0.0)
        ev.run_until(1.0)                       # decode batch warm
        token_t = {}
        gap = [0.0]

        def on_iter(ins, t, _gap=gap, _last=token_t):
            for r in ins.running:
                if not r.prefilling and r.req.req_id < 4:
                    if r.req.req_id in _last:
                        _gap[0] = max(_gap[0], t - _last[r.req.req_id])
                    _last[r.req.req_id] = t

        inst.on_iteration_end = on_iter
        inst.enqueue(SimRequest(req=Request(9, 1.0, 32_768, 4),
                                length=32_768), ev.now)
        ev.run_until(ev.now + 60.0)
        gaps[name] = gap[0]
    # chunked: gaps stay ~one mixed iteration; mono: one gap is the whole
    # 32K prefill (~2s in this profile)
    assert gaps["mono"] > 1.0
    assert gaps["chunked"] < gaps["mono"] / 5
    assert gaps["chunked"] < 0.2


def test_sim_chunked_admission_respects_capacity():
    """Admission must reserve the UNWRITTEN remainder of already-admitted
    prompts: chunks only land at iteration end, so without the pending
    reservation two prompts could both pass the gate and overflow
    capacity once their chunks materialize."""
    from repro.sim.costmodel import profile_from_config
    from repro.sim.events import EventQueue
    from repro.sim.instance import Instance, SimRequest
    from repro.sim.workload import Request

    prof = profile_from_config(get_config("llama3.2-3b"))
    ev = EventQueue()
    inst = Instance(0, prof, 128, ev, prefill_budget=256)
    low = [0.0]
    inst.on_iteration_end = lambda ins, t: low.__setitem__(
        0, min(low[0], ins.free_tokens()))
    done = []
    inst.on_request_done = lambda ins, sr, t: done.append(sr)
    for i in range(2):
        inst.enqueue(SimRequest(req=Request(i, 0.0, 100, 4), length=100),
                     0.0)
    ev.run_until(120.0)
    assert len(done) == 2, "both requests must eventually be served"
    assert low[0] >= 0.0, f"capacity overflowed: min free {low[0]}"


def test_mixed_iter_time_reduces_to_decode_iter_time():
    """With no chunks packed, a mixed iteration must price EXACTLY like a
    plain decode iteration under the same backend flag — so chunked-vs-
    monolithic experiments attribute nothing but prefill scheduling to
    chunking."""
    from repro.sim.costmodel import (decode_iter_time, mixed_iter_time,
                                     profile_from_config)
    for ragged in (False, True):
        prof = profile_from_config(get_config("llama3.2-3b"),
                                   ragged_backend=ragged)
        L = [100, 2000, 50]
        assert abs(mixed_iter_time([], L, prof)
                   - decode_iter_time(L, prof)) < 1e-12


def test_longtail_workload_targets_32k_128k():
    from repro.sim.workload import generate_longtail
    reqs = generate_longtail(6.0, 40.0, seed=3)
    tail = [r.input_len for r in reqs if r.input_len >= 32_000]
    assert len(tail) >= 5, "tail too thin to exercise long prompts"
    assert max(r.input_len for r in reqs) <= 131_072
    assert max(tail) > 64_000, "tail should reach deep into 32K-128K"
    body = [r.input_len for r in reqs if r.input_len < 32_000]
    assert len(body) > len(tail), "body must remain the bulk"
