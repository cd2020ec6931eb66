"""Device-resident decode hot loop (DESIGN.md §Decode hot path): the
work-flattened Pallas grid vs. the oracle at extreme length spread, the
one-device-sync-per-step contract, and greedy-token bit-parity of the
device-resident engine loop against the host-driven reference — on a mock
model (pure plumbing) and the real reduced model."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serving.engine as engine_mod
from repro.configs import get_config
from repro.kernels.cost import (decode_attn_time_flat_s, flat_grid_blocks,
                                pow2_bucket, ragged_blocks)
from repro.kernels.decode_attention import (flat_work_list,
                                            paged_decode_attention_flat)
from repro.kernels.ref import decode_attention_ref
from repro.models import build_model
from repro.models.model import Model
from repro.serving.block_pool import blocks_for
from repro.serving.engine import Engine
from repro.serving.request import ServeRequest, State

RNG = np.random.default_rng(7)


# --------------------------------------------------------------------------
# Flat-grid kernel vs. oracle
# --------------------------------------------------------------------------
def _paged_case(lengths, S, H, Hkv, Dh, BS, dtype):
    """Contiguous KV per request, scattered into a shuffled physical pool."""
    B = len(lengths)
    q = RNG.normal(0, 1, (B, H, Dh)).astype(np.float32)
    k = RNG.normal(0, 1, (B, S, Hkv, Dh)).astype(np.float32)
    v = RNG.normal(0, 1, (B, S, Hkv, Dh)).astype(np.float32)
    NBT = S // BS
    NB = B * NBT + 3
    perm = RNG.permutation(NB)
    k_pool = np.zeros((NB, Hkv, BS, Dh), np.float32)   # head-major pool
    v_pool = np.zeros((NB, Hkv, BS, Dh), np.float32)
    bt = np.zeros((B, NBT), np.int32)
    pi = 0
    for b, L in enumerate(lengths):
        for j in range(blocks_for(L, BS)):
            pb = int(perm[pi]); pi += 1
            bt[b, j] = pb
            k_pool[pb] = k[b, j * BS:(j + 1) * BS].swapaxes(0, 1)
            v_pool[pb] = v[b, j * BS:(j + 1) * BS].swapaxes(0, 1)
    to = lambda a: jnp.asarray(a, dtype)
    return (to(q), to(k), to(v), to(k_pool), to(v_pool),
            jnp.asarray(bt), jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 1e-2)])
def test_flat_kernel_matches_ref_128x_spread(dtype, tol):
    """Acceptance: 128x length spread (4..512) including a single-token
    request and exact full-block-boundary lengths (64, 256, 512)."""
    lengths = [4, 512, 1, 64, 377, 256]
    q, k, v, kp, vp, bt, ls = _paged_case(lengths, 512, 8, 2, 64, 64, dtype)
    ref = decode_attention_ref(q, k, v, ls)
    total = sum(math.ceil(l / 64) for l in lengths)
    for W in (total, pow2_bucket(total), None):
        out = paged_decode_attention_flat(q, kp, vp, bt, ls, num_work=W,
                                          interpret=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)


def test_flat_kernel_dead_slots_and_mqa():
    """lengths==0 rows (dead engine slots) produce zero work items and do
    not disturb live rows' outputs."""
    lengths = [0, 7, 0, 129, 1]
    q, k, v, kp, vp, bt, ls = _paged_case(lengths, 256, 8, 1, 128, 32,
                                          jnp.float32)
    ref = decode_attention_ref(q, k, v, ls)
    out = paged_decode_attention_flat(q, kp, vp, bt, ls, num_work=8,
                                      interpret=True)
    live = [1, 3, 4]
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=3e-5, rtol=3e-5)


def test_flat_work_list_structure():
    """Real prefix enumerates (request, block) request-major in block
    order; the padding tail aliases the LAST request with-work, with
    sentinel block index NBT (always skipped by the length guard)."""
    lengths = jnp.asarray([5, 0, 33, 16], jnp.int32)   # BS=16 -> 1,0,3,1
    wr, wb = flat_work_list(lengths, nbt=4, block_s=16, num_work=8)
    wr, wb = np.asarray(wr), np.asarray(wb)
    np.testing.assert_array_equal(wr[:5], [0, 2, 2, 2, 3])
    np.testing.assert_array_equal(wb[:5], [0, 0, 1, 2, 0])
    np.testing.assert_array_equal(wr[5:], [3, 3, 3])   # aliases last request
    np.testing.assert_array_equal(wb[5:], [4, 4, 4])   # sentinel = NBT


def test_cost_model_flat_terms():
    lengths = [1, 16, 512]
    assert ragged_blocks(lengths, 512) == 3
    assert flat_grid_blocks(lengths, 512) == 4            # pow2 bucket
    assert flat_grid_blocks(lengths, 512, bucketed=False) == 3
    spec_lengths = [32] * 15 + [4096]
    from repro.kernels.cost import AttnSpec, decode_attn_time_s
    spec = AttnSpec(num_q_heads=32, num_kv_heads=8, head_dim=128)
    flat = decode_attn_time_flat_s(spec_lengths, spec)
    padded = decode_attn_time_s(spec_lengths, spec, ragged=False)
    assert flat < padded / 4     # the heterogeneity tax, removed


# --------------------------------------------------------------------------
# Mock model: pure plumbing parity (token_{t+1} = f(token_t, pos_t))
# --------------------------------------------------------------------------
MOCK_VOCAB = 97


def _mock_next(tok, pos):
    return (31 * tok + 7 * pos + 3) % MOCK_VOCAB


def make_mock_model():
    cfg = get_config("smollm-360m").reduced()

    def init(rng):
        return {}

    def _logits(tok, pos):
        return jax.nn.one_hot(_mock_next(tok, pos), MOCK_VOCAB)

    def prefill(params, batch, cache_len=None):
        tokens = batch["tokens"]                      # [1, T]
        T = tokens.shape[1]
        piece = {"kv": jnp.zeros((1, 1, T, 1, 1), jnp.float32)}
        return _logits(tokens[:, -1], jnp.full((1,), T - 1)), piece

    def prefill_bucketed(params, batch, true_len):
        tokens = batch["tokens"]                      # [1, P] padded
        P = tokens.shape[1]
        last = jnp.take_along_axis(tokens, true_len[None, None] - 1,
                                   axis=1)[:, 0]
        piece = {"kv": jnp.zeros((1, 1, P, 1, 1), jnp.float32)}
        return _logits(last, true_len[None] - 1), piece

    def decode_step_paged(params, pool, token, block_tables, pos, **extras):
        return _logits(token, pos), pool

    def decode_step(params, cache, token, pos, **extras):
        return _logits(token, pos), cache

    def init_paged_cache(num_blocks, block_size):
        return {"kv": jnp.zeros((1, num_blocks, 1, block_size, 1),
                                jnp.float32)}

    def init_cache(batch, seq):
        return {"kv": jnp.zeros((1, batch, seq, 1, 1), jnp.float32)}

    return Model(cfg, init, loss=None, prefill=prefill,
                 decode_step=decode_step, init_cache=init_cache,
                 init_paged_cache=init_paged_cache,
                 decode_step_paged=decode_step_paged,
                 prefill_bucketed=prefill_bucketed)


def _mock_reqs(n=5, seed=1):
    r = np.random.default_rng(seed)
    plens = [3, 9, 1, 17, 6, 12, 4][:n]
    news = [7, 2, 11, 1, 9, 5, 8][:n]
    return [ServeRequest(i, r.integers(0, MOCK_VOCAB, p).astype(np.int32), m)
            for i, (p, m) in enumerate(zip(plens, news))]


def _drain(eng, reqs, burst=1, max_iters=400):
    for r in reqs:
        eng.submit(r)
    out = []
    for _ in range(max_iters):
        out += eng.step(burst)
        if len(out) == len(reqs):
            return out
    raise AssertionError("engine did not drain")


@pytest.mark.parametrize("burst", [1, 8])
def test_mock_engine_bit_parity_device_vs_host(burst):
    """Fixed trace, mock model: the device-resident loop (single-step and
    lax.scan fused) emits exactly the host loop's greedy tokens, steps,
    and finish bookkeeping — including max_new_tokens=1 requests that
    finish at prefill."""
    model = make_mock_model()
    runs = {}
    for mode, b in (("host", 1), ("device", burst)):
        eng = Engine(0, model, {}, max_slots=3, max_seq=32,
                     device_resident=(mode == "device"))
        reqs = _mock_reqs()
        _drain(eng, reqs, burst=b)
        runs[mode] = ([list(r.generated) for r in reqs],
                      [r.finish_step for r in reqs],
                      [r.first_token_step for r in reqs],
                      eng.steps, eng.tokens_out)
    assert runs["host"][0] == runs["device"][0]        # tokens, bit-equal
    assert runs["host"] == runs["device"]              # all bookkeeping


def test_mock_engine_eos_mid_burst_parity():
    """eos finishes are data-dependent, so the fused micro-batch decodes
    past them and truncates at the sync — the visible result must equal
    the host loop's."""
    model = make_mock_model()
    prompt = np.asarray([5, 11, 2], np.int32)
    # pick eos = the 3rd greedy token of this trace so it hits mid-burst
    probe = Engine(0, model, {}, max_slots=1, max_seq=32)
    pr = ServeRequest(0, prompt.copy(), 10)
    _drain(probe, [pr])
    eos = pr.generated[2]
    outs = {}
    for mode, burst in (("host", 1), ("device", 8)):
        eng = Engine(0, model, {}, max_slots=1, max_seq=32,
                     device_resident=(mode == "device"))
        r = ServeRequest(0, prompt.copy(), 10, eos_token=eos)
        _drain(eng, [r], burst=burst)
        outs[mode] = (list(r.generated), r.finish_step)
    assert outs["host"] == outs["device"]


def test_engine_one_device_sync_per_step(monkeypatch):
    """Acceptance: Engine.step() performs exactly one device->host
    transfer per step (counted through the d2h shim), admissions
    included; a fused burst still costs one."""
    model = make_mock_model()
    calls = []
    real = engine_mod.d2h
    monkeypatch.setattr(engine_mod, "d2h", lambda x: calls.append(1) or real(x))
    eng = Engine(0, model, {}, max_slots=3, max_seq=64)
    reqs = _mock_reqs(3)
    for r in reqs:
        eng.submit(r)
    eng.step()                       # admission + prefill + decode step
    assert len(calls) == 1
    for _ in range(4):               # steady-state decode
        calls.clear()
        eng.step()
        assert len(calls) == 1
    calls.clear()
    eng.step(8)                      # fused micro-batch: still one sync
    assert len(calls) == 1


def test_engine_grid_accounting_16way_hetero():
    """Acceptance: on a 16-way heterogeneous batch the flat grid runs
    Σ_b ceil(L_b/BS) items (± pow2 bucket padding) where the old grid ran
    B·max_b ceil(L_b/BS)."""
    model = make_mock_model()
    plens = [2, 2, 3, 4, 4, 6, 8, 8, 12, 16, 24, 32, 48, 64, 96, 120]
    eng = Engine(0, model, {}, max_slots=16, max_seq=256, block_size=16)
    reqs = [ServeRequest(i, np.full(p, 1, np.int32), 4)
            for i, p in enumerate(plens)]
    for r in reqs:
        eng.submit(r)
    eng.step()           # one decode launch (whole-prompt admission first)
    flat, real = eng.work_items, eng.real_work_items
    expect = sum(blocks_for(p + 1, 16) for p in plens)
    # the padded grid: every row as long as the longest
    padded = len(plens) * max(blocks_for(p + 1, 16) for p in plens)
    assert real == expect
    assert expect <= flat < 2 * expect                 # pow2 bucket only
    assert padded == 16 * blocks_for(121, 16)
    assert real < padded / 3                           # the heterogeneity tax
    assert flat <= padded / 2                          # survives pow2 padding


# --------------------------------------------------------------------------
# Real model: device loop + kernel backends
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_real_engine_bit_parity_device_vs_host(setup, rng):
    cfg, model, params = setup
    prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in (5, 17, 12)]
    outs = []
    for device_resident in (False, True):
        eng = Engine(0, model, params, max_slots=3, max_seq=64,
                     device_resident=device_resident)
        reqs = [ServeRequest(i, p.copy(), 8) for i, p in enumerate(prompts)]
        _drain(eng, reqs)
        outs.append([list(r.generated) for r in reqs])
    assert outs[0] == outs[1]


def test_real_prefill_bucketed_matches_unpadded(setup, rng):
    """Padding the prompt to a pow2 bucket must not change the last-token
    logits or the written KV rows (causality)."""
    cfg, model, params = setup
    T = 13
    toks = rng.integers(0, cfg.vocab_size, (1, T)).astype(np.int32)
    ref_logits, ref_piece = model.prefill(
        params, {"tokens": jnp.asarray(toks)}, cache_len=T)
    P = pow2_bucket(T)
    padded = np.zeros((1, P), np.int32)
    padded[0, :T] = toks
    logits, piece = model.prefill_bucketed(
        params, {"tokens": jnp.asarray(padded)}, jnp.int32(T))
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               atol=2e-5, rtol=2e-5)
    # the first T KV rows (what the engine scatters into blocks) match too
    for a, b in zip(jax.tree.leaves(piece), jax.tree.leaves(ref_piece)):
        np.testing.assert_allclose(np.asarray(a, np.float32)[:, :, :T],
                                   np.asarray(b, np.float32)[:, :, :T],
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("backend", ["grid", "flat"])
def test_real_model_kernel_backend_matches_dense(setup, rng, backend):
    """forward_decode_paged through the Pallas kernels (interpret mode)
    agrees with the dense-gather XLA path."""
    cfg, model, params = setup
    eng = Engine(0, model, params, max_slots=2, max_seq=64,
                 attn_backend=backend)
    # off-TPU the kernels run interpreted; on TPU they compile for real
    assert eng.attn_interpret == (jax.default_backend() != "tpu")
    ref = Engine(0, model, params, max_slots=2, max_seq=64,
                 attn_backend="dense")
    prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in (6, 21)]
    outs = []
    for e in (eng, ref):
        reqs = [ServeRequest(i, p.copy(), 6) for i, p in enumerate(prompts)]
        _drain(e, reqs)
        outs.append([list(r.generated) for r in reqs])
    assert outs[0] == outs[1]


def test_device_engine_migration_roundtrip(setup, rng):
    """export -> evict -> import across device-resident engines keeps the
    greedy continuation identical (device mirrors re-seeded on import)."""
    cfg, model, params = setup
    mk = lambda i: Engine(i, model, params, max_slots=2, max_seq=64)
    src, dst, ref_eng = mk(0), mk(1), mk(2)
    prompt = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    r = ServeRequest(0, prompt.copy(), 10)
    ref = ServeRequest(9, prompt.copy(), 10)
    src.submit(r)
    ref_eng.submit(ref)
    for _ in range(3):
        src.step()
        ref_eng.step()
    req, piece, _ = src.export_slot(r.slot)
    assert dst.import_request(req, piece)
    src.evict_slot(0)
    while r.state != State.FINISHED:
        dst.step()
    while ref.state != State.FINISHED:
        ref_eng.step()
    assert r.generated == ref.generated
