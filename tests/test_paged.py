"""Block-granular paged KV cache, end to end: kernel vs. oracle, allocator
invariants, paged-vs-monolithic model numerics, block-budget engine
accounting, and the migration round-trip (paged AND monolithic paths)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.migration import gather_kv_blocks, kv_bytes, scatter_kv_blocks
from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.ref import decode_attention_ref
from repro.models import build_model
from repro.serving.block_pool import BlockAllocator, blocks_for
from repro.serving.engine import Engine
from repro.serving.request import ServeRequest, State

RNG = np.random.default_rng(0)


# --------------------------------------------------------------------------
# Kernel: block-table grid vs. the monolithic oracle
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
def test_paged_kernel_matches_ref_hetero(dtype, tol):
    """Acceptance: lengths spanning >= 8x (32..512), bf16 atol <= 1e-2,
    physical blocks deliberately shuffled to exercise the indirection."""
    lengths = [32, 100, 512, 64, 377]
    q, k, v, kp, vp, bt, ls = _paged_case(lengths, 512, 8, 2, 64, 64, dtype)
    ref = decode_attention_ref(q, k, v, ls)
    out = paged_decode_attention(q, kp, vp, bt, ls, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_paged_kernel_mqa_and_odd_blocks():
    lengths = [1, 7, 129]
    q, k, v, kp, vp, bt, ls = _paged_case(lengths, 256, 8, 1, 128, 32,
                                          jnp.float32)
    ref = decode_attention_ref(q, k, v, ls)
    out = paged_decode_attention(q, kp, vp, bt, ls, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


# --------------------------------------------------------------------------
# Allocator invariants
# --------------------------------------------------------------------------
def test_block_allocator_invariants():
    a = BlockAllocator(num_blocks=8, block_size=16)
    assert a.free_tokens() == 128 and a.allocated_blocks == 0
    assert a.can_reserve(8) and not a.can_reserve(9)
    a.reserve(5)
    ids = a.allocate(3)
    assert len(set(ids)) == 3 and a.allocated_blocks == 3
    assert a.free_blocks == 5 and a.reserved_blocks == 5
    # reservations cap admissions, not physical blocks
    assert not a.can_reserve(4) and a.can_reserve(3)
    a.free(ids[:2])
    assert a.allocated_blocks == 1
    a.unreserve(4)
    assert a.reserved_blocks == 1
    with pytest.raises(AssertionError):
        a.free(ids[:1])                # double free
    with pytest.raises(AssertionError):
        a.allocate(99)                 # over-allocate


def test_blocks_for():
    assert blocks_for(0, 16) == 0
    assert blocks_for(1, 16) == 1
    assert blocks_for(16, 16) == 1
    assert blocks_for(17, 16) == 2


def test_gather_scatter_blocks_roundtrip(rng):
    pool = {"k": jnp.asarray(rng.normal(0, 1, (2, 6, 4, 3, 8)), jnp.float32)}
    piece = gather_kv_blocks(pool, [4, 1])
    assert piece["k"].shape == (2, 2, 4, 3, 8)
    dst = {"k": jnp.zeros_like(pool["k"])}
    merged = scatter_kv_blocks(dst, piece, [0, 5])
    assert jnp.array_equal(merged["k"][:, 0], pool["k"][:, 4])
    assert jnp.array_equal(merged["k"][:, 5], pool["k"][:, 1])


# --------------------------------------------------------------------------
# Model + engine: paged vs. monolithic
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _req(rng, cfg, rid, plen=12, new=10):
    return ServeRequest(rid, rng.integers(0, cfg.vocab_size, plen)
                        .astype(np.int32), new)


def _run_engine(eng, reqs, max_steps=400):
    for r in reqs:
        eng.submit(r)
    done = []
    for _ in range(max_steps):
        done += eng.step()
        assert eng.free_tokens() >= 0
        if len(done) == len(reqs):
            break
    return done


def test_paged_engine_matches_monolithic_generation(setup, rng):
    """Same prompts through the paged and the slot-slab engine produce
    identical greedy generations — block tables are numerics-neutral."""
    cfg, model, params = setup
    prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in (5, 17, 33, 12)]
    outs = []
    for paged in (True, False):
        eng = Engine(0, model, params, max_slots=4, max_seq=64, paged=paged)
        reqs = [ServeRequest(i, p.copy(), 8) for i, p in enumerate(prompts)]
        done = _run_engine(eng, reqs)
        assert len(done) == 4
        outs.append([r.generated for r in sorted(reqs, key=lambda r: r.req_id)])
    assert outs[0] == outs[1]


def test_paged_engine_pins_fewer_bytes_on_heterogeneous_batch(setup, rng):
    """The point of paging: a 16-token request pins ~16 tokens of cache,
    not a max_seq slab."""
    cfg, model, params = setup
    prompts = [4, 4, 4, 40]
    mk = lambda: [_req(rng, cfg, i, plen=p, new=4)
                  for i, p in enumerate(prompts)]
    peak = {}
    for paged in (True, False):
        eng = Engine(0, model, params, max_slots=4, max_seq=128, paged=paged)
        _run_engine(eng, mk())
        peak[paged] = eng.peak_kv_bytes
    assert peak[True] < peak[False], peak


def test_paged_engine_incremental_block_growth(setup, rng):
    """A request crossing block boundaries allocates blocks one at a time
    and frees them all on release."""
    cfg, model, params = setup
    eng = Engine(0, model, params, max_slots=1, max_seq=64, paged=True,
                 block_size=4)
    r = _req(rng, cfg, 0, plen=6, new=10)   # grows 7 -> 16 tokens
    eng.submit(r)
    eng.step()
    assert len(eng.block_tables[0]) == blocks_for(6, 4)
    seen = set()
    while r.state != State.FINISHED:
        seen.add(len(eng.block_tables[0]))
        eng.step()
    assert max(seen) == blocks_for(16, 4)
    assert eng.allocator.allocated_blocks == 0     # all freed
    assert eng.allocator.reserved_blocks == 0


def test_admission_respects_block_budget(setup, rng):
    """Unified accounting: admission gates on worst-case reservations, so
    the free budget is non-negative at every step (the old engine's
    admission and used_tokens() disagreed)."""
    cfg, model, params = setup
    eng = Engine(0, model, params, max_slots=4, max_seq=64, token_budget=40,
                 paged=True, block_size=16)
    reqs = [_req(rng, cfg, i, plen=16, new=4) for i in range(3)]
    done = _run_engine(eng, reqs)
    assert len(done) == 3                        # drains eventually
    assert eng.reserved_tokens() == 0


# --------------------------------------------------------------------------
# Migration round-trip (satellite: bit-identical logits, both layouts)
# --------------------------------------------------------------------------
def _next_logits(model, eng, req):
    """Next-token logits for a running request, computed from the engine's
    exported wire piece (contiguous [L, 1, len, ...])."""
    _, piece, _ = eng.export_slot(req.slot)
    cache = model.init_cache(1, eng.max_seq)
    cache = jax.tree.map(
        lambda a, p: a.at[:, :, :p.shape[2]].set(p.astype(a.dtype)),
        cache, piece)
    tok = jnp.asarray([req.generated[-1]], jnp.int32)
    pos = jnp.asarray([req.length - 1], jnp.int32)
    logits, _ = model.decode_step(model_params(eng), cache, tok, pos)
    return np.asarray(logits[0])


def model_params(eng):
    return eng.params


@pytest.mark.parametrize("paged", [True, False])
def test_migration_roundtrip_bit_identical_logits(setup, rng, paged):
    """export_slot -> evict_slot -> import_request on a second engine must
    produce bit-identical next-token logits vs. never migrating."""
    cfg, model, params = setup
    mk = lambda i: Engine(i, model, params, max_slots=2, max_seq=64,
                          paged=paged)
    src, dst, ref_eng = mk(0), mk(1), mk(2)
    prompt = rng.integers(0, cfg.vocab_size, 13).astype(np.int32)
    r = ServeRequest(0, prompt.copy(), 12)
    ref = ServeRequest(9, prompt.copy(), 12)
    src.submit(r)
    ref_eng.submit(ref)
    for _ in range(4):
        src.step()
        ref_eng.step()
    src_slot = r.slot          # import_request reassigns r.slot to dst's
    req, piece, nbytes = src.export_slot(src_slot)
    # wire piece is trimmed to the written rows (length-1), not max_seq
    assert nbytes == pytest.approx(
        kv_bytes(model.init_cache(1, src.max_seq))
        * (r.length - 1) / src.max_seq)
    assert dst.import_request(req, piece)
    src.evict_slot(src_slot)
    assert dst.slots[r.slot] is r
    assert dst.id in r.tokens_by_engine          # ledger updated on import
    lg_mig = _next_logits(model, dst, r)
    lg_ref = _next_logits(model, ref_eng, ref)
    np.testing.assert_array_equal(lg_mig, lg_ref)
    # and the continued decode stays greedy-identical to completion
    while r.state != State.FINISHED:
        dst.step()
    while ref.state != State.FINISHED:
        ref_eng.step()
    assert r.generated == ref.generated


def test_import_rejects_overflow(setup, rng):
    """A migrated-in request whose remaining generation cannot fit max_seq
    is refused instead of silently truncated."""
    cfg, model, params = setup
    src = Engine(0, model, params, max_slots=2, max_seq=128)
    dst = Engine(1, model, params, max_slots=2, max_seq=32)
    r = _req(rng, cfg, 0, plen=16, new=40)       # needs up to 56 tokens
    src.submit(r)
    src.step()
    req, piece, _ = src.export_slot(r.slot)
    assert not dst.import_request(req, piece)
    assert dst.free_tokens() == dst.token_budget  # nothing leaked


def test_oversized_prompt_rejected_not_wedged(setup, rng):
    """A prompt that can never fit max_seq is failed (rejected=True)
    instead of blocking the FCFS queue forever behind it."""
    cfg, model, params = setup
    eng = Engine(0, model, params, max_slots=2, max_seq=32)
    big = _req(rng, cfg, 0, plen=40, new=4)
    ok = _req(rng, cfg, 1, plen=8, new=4)
    done = _run_engine(eng, [big, ok])
    assert len(done) == 2
    assert big.rejected and big.generated == []
    assert not ok.rejected and len(ok.generated) == 4


def test_import_rejects_when_budget_reserved(setup, rng):
    cfg, model, params = setup
    src = Engine(0, model, params, max_slots=2, max_seq=64)
    dst = Engine(1, model, params, max_slots=2, max_seq=64,
                 token_budget=32, block_size=16)
    big = _req(rng, cfg, 1, plen=20, new=8)      # reserves 2 blocks = all
    dst.submit(big)
    dst.step()
    r = _req(rng, cfg, 0, plen=12, new=8)
    src.submit(r)
    src.step()
    req, piece, _ = src.export_slot(r.slot)
    assert not dst.import_request(req, piece)


@pytest.mark.parametrize("quant", [False, True])
def test_wire_piece_block_layout_roundtrip(rng, quant):
    """Contiguous wire piece [L, 1, T, Hkv, Dh] -> head-major lane-padded
    pool blocks [L, nb, Hkv, BS, Dp] -> back is the identity on the T
    real rows; the padded lanes and tail rows are zeros."""
    from repro.models.attention import (KVCache, blocks_to_piece,
                                        piece_to_blocks, pool_row_width,
                                        quantize_piece)
    L, T, Hkv, Dh, BS = 2, 21, 3, 64, 8
    x = jnp.asarray(rng.normal(0, 1, (L, 1, T, Hkv, Dh)), jnp.float32)
    piece = KVCache(x, 2 * x)
    if quant:
        piece = quantize_piece(piece)
    nb = blocks_for(T, BS)
    blocks = piece_to_blocks(piece, nb, BS, pool_row_width(Dh))
    assert blocks.k.shape == (L, nb, Hkv, BS, 128)
    assert float(jnp.abs(blocks.k[..., Dh:]).max()) == 0
    # token t of head h lands at block t // BS, row t % BS
    np.testing.assert_array_equal(np.asarray(blocks.k[:, 1, 2, 3, :Dh]),
                                  np.asarray(piece.k[:, 0, BS + 3, 2]))
    if quant:
        assert blocks.k_scale.shape == (L, nb, Hkv, BS)
    back = blocks_to_piece(blocks, Dh)
    for got, want in zip(back, piece):
        np.testing.assert_array_equal(np.asarray(got[:, :, :T]),
                                      np.asarray(want))
        assert float(jnp.abs(got[:, :, T:]).max()) == 0
