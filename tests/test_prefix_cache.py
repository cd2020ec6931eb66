"""Refcounted prefix-cached KV pool, end to end (DESIGN.md §Prefix cache):
allocator share/release/reclaim invariants (unit + hypothesis random
interleavings), the aliased-block-table decode-kernel oracle (shared
physical blocks in multiple tables — zero kernel changes), warm-vs-cold
engine acceptance (bit-identical tokens, >= 90% of prefill block-work
skipped), tail-only admission reservations, LRU reclaim, and the
migrated-shared-prefix round trip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.kernels.cost import AttnSpec, prefill_flops, prefill_flops_skipped
from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_decode_attention_flat)
from repro.kernels.ref import decode_attention_ref
from repro.models import build_model
from repro.serving.block_pool import (BlockAllocator, blocks_for, chain_hash,
                                      prompt_chain)
from repro.serving.engine import Engine
from repro.serving.request import ServeRequest, State

RNG = np.random.default_rng(11)


# --------------------------------------------------------------------------
# Chain hashing
# --------------------------------------------------------------------------
def test_prompt_chain_is_parent_chained_and_capped():
    p = np.arange(40, dtype=np.int32)
    full = prompt_chain(p, 16)
    assert len(full) == 2                       # 40 tokens -> 2 full blocks
    assert full[0] == chain_hash(0, p[:16])
    assert full[1] == chain_hash(full[0], p[16:32])
    # identical prefixes chain identically; divergence breaks the chain
    q = p.copy()
    q[20] += 1
    qc = prompt_chain(q, 16)
    assert qc[0] == full[0] and qc[1] != full[1]
    # the lookup cap leaves >= 1 token to prefill: a 32-token prompt may
    # share at most 1 block
    assert len(prompt_chain(p[:32], 16, limit=(32 - 1) // 16)) == 1


# --------------------------------------------------------------------------
# Allocator: share / release / publish / reclaim
# --------------------------------------------------------------------------
def test_share_release_refcounts_and_revival():
    a = BlockAllocator(num_blocks=8, block_size=16)
    a.reserve(3)
    ids = a.allocate(3)
    digests = [chain_hash(0, [1] * 16)]
    assert a.publish(ids[0], digests[0], head=True)
    assert not a.publish(ids[1], digests[0])    # first writer wins
    a.share([ids[0]])                           # second reference
    assert a.ref(ids[0]) == 2
    # owner leaves; the shared cached block stays resident, counted once
    a.release(ids, owned=True)
    a.unreserve(3)
    assert a.allocated_blocks == 1 and a.ref(ids[0]) == 1
    assert a.free_blocks == 7                   # 2 freed + 5 never used
    # last sharer leaves: the block parks reclaimable (still free capacity)
    a.release([ids[0]], owned=False)
    assert a.allocated_blocks == 0 and a.free_blocks == 8
    assert a.lookup(digests) == [ids[0]]        # still servable
    # revival: share straight out of the reclaimable LRU
    a.share([ids[0]])
    assert a.ref(ids[0]) == 1 and a.allocated_blocks == 1
    a.release([ids[0]], owned=False)
    a.check_invariants()


def test_double_free_asserts_with_free_set():
    a = BlockAllocator(num_blocks=4, block_size=16)
    a.reserve(2)
    ids = a.allocate(2)
    a.free(ids)
    for b in ids:
        with pytest.raises(AssertionError):
            a.free([b])
    a.check_invariants()


def test_lru_reclaim_evicts_oldest_cached_never_referenced():
    a = BlockAllocator(num_blocks=6, block_size=4)
    ha = prompt_chain(np.arange(8, dtype=np.int32), 4)
    hb = prompt_chain(np.arange(8, 16, dtype=np.int32), 4)
    a.reserve(2)
    ia = a.allocate(2)
    for j, h in enumerate(ha):
        a.publish(ia[j], h, head=(j == 0))
    a.release(ia)
    a.unreserve(2)
    a.reserve(2)
    ib = a.allocate(2)
    for j, h in enumerate(hb):
        a.publish(ib[j], h, head=(j == 0))
    a.release(ib)
    a.unreserve(2)
    assert a.free_blocks == 6 and a.cached_blocks == 4
    # revive chain B: its blocks are referenced and must survive reclaim
    a.share(a.lookup(hb))
    a.reserve(4)
    got = a.allocate(4)                 # 2 free + reclaim both of chain A
    assert a.cache_evictions == 2
    assert a.lookup(ha) == []                   # A evicted, LRU first
    assert a.lookup(hb) == ib                   # B referenced: untouched
    assert set(got).isdisjoint(ib)
    a.check_invariants()
    with pytest.raises(AssertionError):
        a.allocate(1)                   # nothing reclaimable is referenced


# --------------------------------------------------------------------------
# Hypothesis: random share/release/reclaim interleavings
# --------------------------------------------------------------------------
def _run_random_program(seed: int, num_blocks: int, n_ops: int) -> None:
    """Engine-shaped random program over a tiny prompt alphabet (chains
    collide constantly): after every op — admit-with-lookup, incremental
    growth, publish, finish — the allocator holds
    free + allocated == num_blocks, no block is both free and referenced,
    nothing double-frees, and reclaim never evicts a referenced block
    (``check_invariants`` + the allocator's own asserts)."""
    rng = np.random.default_rng(seed)
    BS = 4
    a = BlockAllocator(num_blocks, BS)
    live = {}            # rid -> [digests, shared_ids, owned_ids, reserved]
    published = set()
    rid = 0
    for _ in range(n_ops):
        ops = ["admit"]
        if live:
            ops += ["grow", "publish", "finish"]
        op = ops[rng.integers(0, len(ops))]
        if op == "admit":
            nblk = int(rng.integers(1, 5))
            prompt = np.repeat(rng.integers(0, 3, nblk).astype(np.int32),
                               BS)
            digests = prompt_chain(prompt, BS)
            worst = nblk + int(rng.integers(0, 3))        # growth headroom
            chain = a.lookup(digests)
            # the engine's gate: tail reservation + revival charge for
            # parked (refcount-0) chain blocks share() is about to revive
            if not a.can_reserve(worst - len(chain)
                                 + a.revival_cost(chain)):
                continue
            a.reserve(worst - len(chain))
            if chain:
                a.share(chain)
            owned = a.allocate(nblk - len(chain))
            live[rid] = [digests, list(chain), owned, worst - len(chain)]
            rid += 1
        elif op == "grow":
            r = sorted(live)[rng.integers(0, len(live))]
            _, _, owned, reserved = live[r]
            if reserved > len(owned):       # still covered: cannot fail
                owned.extend(a.allocate(1))
        elif op == "publish":
            r = sorted(live)[rng.integers(0, len(live))]
            if r in published:
                continue
            published.add(r)
            digests, shared, owned, _ = live[r]
            table = shared + owned
            for j, h in enumerate(digests):
                a.publish(table[j], h, head=(j == 0))
        else:   # finish
            r = sorted(live)[rng.integers(0, len(live))]
            digests, shared, owned, reserved = live.pop(r)
            if shared:
                a.release(shared, owned=False)
            if owned:
                a.release(owned, owned=True)
            a.unreserve(reserved)
        a.check_invariants()
        assert a.allocated_blocks + a.free_blocks == a.num_blocks
        assert a.free_tokens() >= 0
    for r in sorted(live):                      # drain
        digests, shared, owned, reserved = live[r]
        if shared:
            a.release(shared, owned=False)
        if owned:
            a.release(owned, owned=True)
        a.unreserve(reserved)
        a.check_invariants()
    assert a.allocated_blocks == 0 and a.reserved_blocks == 0


@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 2**16), num_blocks=st.integers(6, 20),
       n_ops=st.integers(1, 60))
def test_allocator_invariants_random_interleavings(seed, num_blocks, n_ops):
    _run_random_program(seed, num_blocks, n_ops)


@pytest.mark.parametrize("seed", range(6))
def test_allocator_invariants_fixed_seeds(seed):
    """The same property on fixed seeds — runs even where hypothesis is
    stubbed out (see conftest shim)."""
    _run_random_program(seed, num_blocks=8 + 2 * seed, n_ops=60)


def test_warm_admission_charges_revival_of_parked_chain(setup, rng):
    """Regression (PR-5 review): sharing a PARKED (refcount-0) cached
    chain revives it into cached_live, so the admission gate must charge
    the revival — otherwise reserved + cached_live can overshoot
    num_blocks and a reservation-covered mid-decode allocation asserts."""
    cfg, model, params = setup
    # 10-block pool: publisher leaves a 4-block parked chain; a cold
    # hog reserves 6 of the 10 blocks; the warm request (worst 5,
    # chain 4, revival 4) must then be REFUSED: 6 + (5-4) + 4 = 11 > 10.
    eng = Engine(0, model, params, max_slots=3, max_seq=256,
                 token_budget=160, block_size=16,
                 prefill_token_budget=64, attn_backend="dense")
    prompt = rng.integers(0, cfg.vocab_size, 70).astype(np.int32)  # 4 full
    pub = ServeRequest(0, prompt.copy(), 10)       # worst 80 -> 5 blocks
    eng.submit(pub)
    while pub.state is not State.FINISHED:
        eng.step()
    assert eng.allocator.cached_blocks == 4        # parked chain
    hog = ServeRequest(1, rng.integers(0, cfg.vocab_size, 60)
                       .astype(np.int32), 36)      # worst 96 -> 6 blocks
    eng.submit(hog)
    eng.step()
    assert hog.state is State.RUNNING
    warm = ServeRequest(2, prompt.copy(), 10)
    assert not eng.can_accept(warm), \
        "revival of the parked chain must be charged against admission"
    eng.submit(warm)
    for _ in range(200):                           # hog drains, warm admits
        eng.step()
        eng.allocator.check_invariants()
        if warm.state is State.FINISHED:
            break
    assert warm.state is State.FINISHED
    assert warm.cached_tokens > 0                  # still served warm later


# --------------------------------------------------------------------------
# Aliased block tables: the zero-kernel-change proof
# --------------------------------------------------------------------------
def _aliased_case(BS, Hkv, Dh, H, shared_blocks, lengths, dtype):
    """Requests 0 and 1 share their first ``shared_blocks`` PHYSICAL
    blocks (one copy in the pool, two tables pointing at it) — exactly
    what the prefix cache produces. The oracle sees the duplicated
    contiguous KV."""
    B = len(lengths)
    NBT = -(-max(lengths) // BS)
    S = NBT * BS                     # block-padded KV rows
    q = RNG.normal(0, 1, (B, H, Dh)).astype(np.float32)
    k = RNG.normal(0, 1, (B, S, Hkv, Dh)).astype(np.float32)
    v = RNG.normal(0, 1, (B, S, Hkv, Dh)).astype(np.float32)
    sh = shared_blocks * BS
    k[1, :sh] = k[0, :sh]            # identical prefix content
    v[1, :sh] = v[0, :sh]
    NB = B * NBT + 2
    perm = RNG.permutation(NB)
    kp = np.zeros((NB, Hkv, BS, Dh), np.float32)       # head-major pool
    vp = np.zeros((NB, Hkv, BS, Dh), np.float32)
    bt = np.zeros((B, NBT), np.int32)
    pi = 0
    for b, L in enumerate(lengths):
        for j in range(blocks_for(L, BS)):
            if b == 1 and j < shared_blocks:
                bt[1, j] = bt[0, j]          # ALIAS: same physical block
                continue
            pb = int(perm[pi]); pi += 1
            bt[b, j] = pb
            kp[pb] = k[b, j * BS:(j + 1) * BS].swapaxes(0, 1)
            vp[pb] = v[b, j * BS:(j + 1) * BS].swapaxes(0, 1)
    to = lambda x: jnp.asarray(x, dtype)
    return (to(q), to(k), to(v), to(kp), to(vp),
            jnp.asarray(bt), jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                       (jnp.bfloat16, 1e-2)])
def test_decode_kernels_with_aliased_block_tables(dtype, tol):
    """Both paged decode kernels (block-table grid and flat work list)
    are bit-for-bit indifferent to two tables sharing physical blocks —
    block tables were always arbitrary, so prefix sharing needs ZERO
    kernel changes."""
    q, k, v, kp, vp, bt, ls = _aliased_case(
        BS=32, Hkv=2, Dh=64, H=8, shared_blocks=3,
        lengths=[200, 137, 64], dtype=dtype)
    ref = decode_attention_ref(q, k, v, ls)
    grid = paged_decode_attention(q, kp, vp, bt, ls, interpret=True)
    flat = paged_decode_attention_flat(q, kp, vp, bt, ls, interpret=True)
    for out in (grid, flat):
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)


# --------------------------------------------------------------------------
# Engine: warm identical prompt — the acceptance criterion
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _drain(eng, req, max_steps=400):
    eng.submit(req)
    for _ in range(max_steps):
        eng.step()
        eng.allocator.check_invariants()
        assert eng.free_tokens() >= 0
        if req.state is State.FINISHED:
            return
    raise AssertionError("request did not finish")


def test_warm_prompt_bit_identical_and_skips_90pct_block_work(setup, rng):
    """ISSUE-5 acceptance: a warm identical-prompt request produces
    bit-identical tokens to the cold run while skipping >= 90% of the
    prefill block-work (cost counters), allocator invariants asserted at
    every step."""
    cfg, model, params = setup
    prompt = rng.integers(0, cfg.vocab_size, 1024).astype(np.int32)
    eng = Engine(0, model, params, max_slots=2, max_seq=2048,
                 block_size=16, prefill_token_budget=32,
                 attn_backend="dense")
    cold = ServeRequest(0, prompt.copy(), 6)
    _drain(eng, cold)
    cold_work = eng.prefill_work_blocks
    assert eng.cached_prompt_tokens_total == 0
    warm = ServeRequest(1, prompt.copy(), 6)
    _drain(eng, warm)
    warm_work = eng.prefill_work_blocks - cold_work
    assert warm.generated == cold.generated, "warm tokens diverged"
    assert eng.cached_prompt_tokens_total == 1008    # 63 of 64 blocks
    skipped = 1.0 - warm_work / cold_work
    assert skipped >= 0.90, f"only {skipped:.1%} of block-work skipped"
    # everything drains: shared blocks released, only cache entries remain
    assert eng.allocator.allocated_blocks == 0
    assert eng.allocator.reserved_blocks == 0
    assert eng.allocator.cached_blocks > 0


def test_prefix_cache_off_is_bit_parity_legacy(setup, rng):
    cfg, model, params = setup
    prompt = rng.integers(0, cfg.vocab_size, 200).astype(np.int32)
    outs = []
    for pc in (True, False):
        eng = Engine(0, model, params, max_slots=2, max_seq=512,
                     block_size=16, prefill_token_budget=64,
                     attn_backend="dense", prefix_cache=pc)
        reqs = [ServeRequest(i, prompt.copy(), 5) for i in range(2)]
        for r in reqs:
            _drain(eng, r)
        outs.append([r.generated for r in reqs])
        if not pc:
            assert eng.cached_prompt_tokens_total == 0
    assert outs[0] == outs[1]


def test_shared_prefix_admits_where_cold_would_not(setup, rng):
    """Tail-only reservations are the capacity win: two long-prefix
    requests run CONCURRENTLY in a pool a cold pair cannot share."""
    cfg, model, params = setup
    prompt = rng.integers(0, cfg.vocab_size, 120).astype(np.int32)
    concurrent = {}
    for pc in (True, False):
        eng = Engine(0, model, params, max_slots=4, max_seq=256,
                     token_budget=192, block_size=16,
                     prefill_token_budget=64, attn_backend="dense",
                     prefix_cache=pc)
        r0 = ServeRequest(0, prompt.copy(), 20)
        eng.submit(r0)
        while r0.first_token_step is None:      # prefill done -> published
            eng.step()
        r1 = ServeRequest(1, prompt.copy(), 20)
        eng.submit(r1)
        eng.step()
        eng.step()
        concurrent[pc] = (r0.state is State.RUNNING
                          and r1.state is State.RUNNING)
        eng.allocator.check_invariants()
        while not (r0.state is State.FINISHED
                   and r1.state is State.FINISHED):
            eng.step()
        assert eng.allocator.allocated_blocks == 0
    assert concurrent[True], "warm request should share the prefix blocks"
    assert not concurrent[False], "cold pair cannot fit: test is vacuous"


def test_migrated_shared_prefix_reimports_private(setup, rng):
    """A request sharing cached prefix blocks migrates mid-decode: the
    receiver re-imports it as private (fresh blocks, true-length
    reservation), tokens stay bit-identical, and the source's cache plus
    refcounts stay consistent."""
    cfg, model, params = setup
    prompt = rng.integers(0, cfg.vocab_size, 100).astype(np.int32)
    mk = lambda i: Engine(i, model, params, max_slots=2, max_seq=256,
                          block_size=16, prefill_token_budget=64,
                          attn_backend="dense")
    src, dst, ref_eng = mk(0), mk(1), mk(2)
    pub = ServeRequest(0, prompt.copy(), 30)      # publisher, keeps running
    src.submit(pub)
    while pub.first_token_step is None:
        src.step()
    warm = ServeRequest(1, prompt.copy(), 12)
    ref = ServeRequest(9, prompt.copy(), 12)
    src.submit(warm)
    ref_eng.submit(ref)
    for _ in range(4):
        src.step()
        ref_eng.step()
    assert warm.cached_tokens > 0, "sharer never hit the cache"
    src_slot = warm.slot               # import_request reassigns warm.slot
    req, piece, _ = src.export_slot(src_slot)
    assert dst.import_request(req, piece)
    src.evict_slot(src_slot)
    src.allocator.check_invariants()
    dst.allocator.check_invariants()
    assert warm.cached_tokens == 0                # private on the receiver
    # publisher's blocks still referenced on the source (pub is running)
    assert src.allocator.allocated_blocks > 0
    while warm.state is not State.FINISHED:
        dst.step()
    while ref.state is not State.FINISHED:
        ref_eng.step()
    assert warm.generated == ref.generated
    while pub.state is not State.FINISHED:
        src.step()
    assert src.allocator.allocated_blocks == 0
    src.allocator.check_invariants()


def test_prefix_hint_and_queued_tokens_use_uncached_length(setup, rng):
    cfg, model, params = setup
    prompt = rng.integers(0, cfg.vocab_size, 160).astype(np.int32)
    eng = Engine(0, model, params, max_slots=1, max_seq=512,
                 block_size=16, prefill_token_budget=64,
                 attn_backend="dense")
    r0 = ServeRequest(0, prompt.copy(), 24)
    _d, c, _p = eng.prefix_hint(r0)
    assert c == 0                                 # cold
    eng.submit(r0)
    while r0.first_token_step is None:
        eng.step()
    digest, cached, promo = eng.prefix_hint(ServeRequest(1, prompt.copy(), 4))
    assert digest == chain_hash(0, prompt[:16])
    assert cached == 144                          # 9 of 10 blocks (cap)
    assert promo == 0                             # all device-resident
    assert digest in eng.prefix_digests()
    # the slot is occupied, so the warm submit waits — queued as its
    # 16-token effective self, not a 160-token prompt
    r1 = ServeRequest(1, prompt.copy(), 4)
    eng.submit(r1)
    assert eng.queued_tokens() == 160 - 144


def test_sim_admission_charges_prefix_revival():
    """Regression (PR-5 review): a published prefix with NO live sharer
    is parked (free capacity) in the sim too, so admitting a warm request
    must charge the revived blocks — otherwise the sim admits past
    capacity where the engine's revival_cost refuses, and free_tokens()
    goes negative."""
    from repro.sim.costmodel import profile_from_config
    from repro.sim.events import EventQueue
    from repro.sim.instance import Instance, SimRequest
    from repro.sim.workload import Request

    prof = profile_from_config(get_config("llama3.2-3b"))
    ev = EventQueue()
    inst = Instance(0, prof, 512.0, ev, block_size=16, prefill_budget=512)
    inst.on_iteration_end = lambda i, t: None
    free_floor = []
    inst.on_request_done = lambda i, r, t: free_floor.append(i.free_tokens())
    grp = dict(prefix_group=0, prefix_len=256)
    r0 = SimRequest(req=Request(0, 0.0, 272, 2, **grp), length=272)
    inst.enqueue(r0, 0.0)
    ev.run_until(ev.now + 1e3)
    assert r0.done and 0 in inst.prefix_digests()
    # hog pins 272 of 512 tokens; the warm arrival needs 16 (tail) + 256
    # (revived prefix) = 272 > 240 free, so it must WAIT
    hog = SimRequest(req=Request(1, 0.0, 260, 40), length=260)
    warm = SimRequest(req=Request(2, 0.0, 272, 2, **grp), length=272)
    inst.enqueue(hog, ev.now)
    inst.enqueue(warm, ev.now)
    assert warm in inst.waiting, "revival of parked prefix was not charged"
    orig_end = inst._end_iteration
    seen_free = []

    def spy(t, admitted):
        orig_end(t, admitted)
        seen_free.append(inst.free_tokens())
    inst._end_iteration = spy
    ev.run_until(ev.now + 1e3)
    assert hog.done and warm.done
    assert warm.cached_tokens == 256
    assert min(seen_free) >= 0, "sim budget went negative"
    assert inst.free_tokens() == inst.capacity


# --------------------------------------------------------------------------
# Cost mirrors
# --------------------------------------------------------------------------
def test_prefill_flops_cached_accounting():
    spec = AttnSpec(8, 2, 64)
    full = prefill_flops(4096, spec)
    warm = prefill_flops(4096, spec, cached_tokens=4080)
    assert warm < 0.01 * full
    assert prefill_flops_skipped(4096, 4080, spec) == pytest.approx(
        full - warm)
    # summing tail-after-cached plus the cached part's own cold prefill
    # recovers the whole-prompt count (chunk-sum identity)
    from repro.kernels.cost import prefill_chunk_flops
    assert prefill_chunk_flops(2048, 0, spec) \
        + prefill_chunk_flops(2048, 2048, spec) \
        == pytest.approx(prefill_flops(4096, spec), rel=1e-6)


def test_shared_prefix_workload_generator():
    from repro.sim.workload import generate_shared_prefix, shared_prefix_spec
    reqs = generate_shared_prefix(shared_prefix_spec(
        4.0, 20.0, seed=3, num_groups=3, prefix_len=512, turns=2))
    assert len(reqs) > 10
    groups = {r.prefix_group for r in reqs}
    assert len(groups) > 1
    for r in reqs:
        assert r.prefix_group >= 0
        assert 0 < r.prefix_len <= r.input_len - 16
    # popular groups repeat — the whole point of prefix caching
    from collections import Counter
    assert Counter(r.prefix_group for r in reqs).most_common(1)[0][1] >= 3


# --------------------------------------------------------------------------
# Multi-tier KV (DESIGN.md §Multi-tier KV): demote / promote / host bound
# --------------------------------------------------------------------------
def _run_random_tiered_program(seed: int, num_blocks: int, host_blocks: int,
                               n_ops: int) -> None:
    """The engine-shaped random program of ``_run_random_program``, with
    the host tier ON and park/unpark in the mix: admissions consume
    two-tier chain hits (share the device run, promote the host run —
    the engine's ``_promote_blocks`` sequence: pop payloads FIRST, then
    allocate under the reservation, then re-publish with chain links).
    After every op the device invariant (``check_invariants`` — which
    also walks the host store: capacity bound, parent residency, single-
    tier residence) and the explicit host capacity bound must hold."""
    rng = np.random.default_rng(seed)
    BS = 4
    a = BlockAllocator(num_blocks, BS, host_blocks=host_blocks)
    a.set_demote_fetch(lambda b: ("snap", b))
    live = {}        # rid -> [digests, shared, owned, reserved, parked?]
    published = set()
    rid = 0
    for _ in range(n_ops):
        ops = ["admit", "materialize"]
        if live:
            ops += ["grow", "publish", "finish", "parkflip"]
        op = ops[rng.integers(0, len(ops))]
        if op == "admit":
            nblk = int(rng.integers(1, 5))
            prompt = np.repeat(rng.integers(0, 3, nblk).astype(np.int32),
                               BS)
            digests = prompt_chain(prompt, BS)
            worst = nblk + int(rng.integers(0, 3))        # growth headroom
            dev, host_run = a.lookup_tiered(digests)
            need = worst - len(dev) + a.revival_cost(dev)
            if not a.can_reserve(need):
                continue
            a.reserve(worst - len(dev))
            if dev:
                a.share(dev)
            # promote: pop payloads BEFORE allocating — the allocation's
            # own reclaim-demotes must never evict what's being promoted
            payloads = [a.host_pop(h) for h in host_run]
            assert all(p is not None for p in payloads)
            owned = a.allocate(nblk - len(dev))
            for j, h in enumerate(host_run):
                d0 = len(dev) + j
                a.publish(owned[j], h, head=(d0 == 0),
                          parent=digests[d0 - 1] if d0 else 0)
            live[rid] = [digests, list(dev), owned, worst - len(dev),
                         None]
            if host_run:
                published.add(rid)      # promoted digests are re-indexed
            rid += 1
        elif op == "materialize":
            a.host_materialize(lambda p: ("mat", p))
        elif op == "grow":
            r = sorted(live)[rng.integers(0, len(live))]
            _, _, owned, reserved, parked = live[r]
            # a parked request is preempted: it never grows until resumed
            if parked is None and reserved > len(owned):  # covered: cannot fail
                owned.extend(a.allocate(1))
        elif op == "publish":
            r = sorted(live)[rng.integers(0, len(live))]
            if r in published:
                continue
            published.add(r)
            digests, shared, owned, _, _ = live[r]
            table = shared + owned
            for j, h in enumerate(digests):
                a.publish(table[j], h, head=(j == 0),
                          parent=digests[j - 1] if j else 0)
        elif op == "parkflip":
            r = sorted(live)[rng.integers(0, len(live))]
            digests, shared, owned, _, parked = live[r]
            if parked is not None:
                a.unpark(parked)            # resume: exact parked snapshot
                live[r][4] = None
            elif shared + owned:
                live[r][4] = list(shared + owned)
                a.park(live[r][4])
        else:   # finish
            r = sorted(live)[rng.integers(0, len(live))]
            digests, shared, owned, reserved, parked = live.pop(r)
            if parked is not None:
                a.unpark(parked)
            if shared:
                a.release(shared, owned=False)
            if owned:
                a.release(owned, owned=True)
            a.unreserve(reserved)
        a.check_invariants()
        assert a.allocated_blocks + a.free_blocks == a.num_blocks
        assert a.host_blocks_used <= host_blocks
        assert a.free_tokens() >= 0
    for r in sorted(live):                      # drain
        digests, shared, owned, reserved, parked = live.pop(r)
        if parked is not None:
            a.unpark(parked)
        if shared:
            a.release(shared, owned=False)
        if owned:
            a.release(owned, owned=True)
        a.unreserve(reserved)
        a.check_invariants()
    assert a.allocated_blocks == 0 and a.reserved_blocks == 0
    assert a.host_blocks_used <= host_blocks
    # the split counters tile the legacy one exactly
    assert a.cache_evictions == a.cache_demotions + a.cache_drops


@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 2**16), num_blocks=st.integers(6, 20),
       host_blocks=st.integers(1, 10), n_ops=st.integers(1, 60))
def test_tiered_allocator_invariants_random_interleavings(
        seed, num_blocks, host_blocks, n_ops):
    _run_random_tiered_program(seed, num_blocks, host_blocks, n_ops)


@pytest.mark.parametrize("seed", range(6))
def test_tiered_allocator_invariants_fixed_seeds(seed):
    """Same property on fixed seeds — runs even where hypothesis is
    stubbed out (see conftest shim)."""
    _run_random_tiered_program(seed, num_blocks=8 + 2 * seed,
                               host_blocks=1 + seed, n_ops=60)


def test_partially_dropped_chain_never_promotes():
    """A chain whose demote was cut short (host tier too small: admitting
    a later block evicted its own ancestors) must never advertise a
    promotable run — ``lookup_tiered`` stops at the first digest in
    neither tier, and the host store drops orphaned descendants rather
    than keeping unreachable payloads."""
    prompt = np.repeat(np.arange(3, dtype=np.int32), 4)
    digests = prompt_chain(prompt, 4)          # 3-block chain
    for cap, want_host in ((3, 3), (2, 0)):
        a = BlockAllocator(num_blocks=4, block_size=4, host_blocks=cap)
        a.set_demote_fetch(lambda b: ("snap", b))
        a.reserve(3)
        ids = a.allocate(3)
        for j, h in enumerate(digests):
            a.publish(ids[j], h, head=(j == 0),
                      parent=digests[j - 1] if j else 0)
        a.release(ids)
        a.unreserve(3)                         # chain parked, reclaimable
        a.reserve(4)
        a.allocate(4)                          # reclaims the whole chain
        a.check_invariants()
        # cap 3: whole chain demotes -> fully promotable. cap 2: block 3's
        # put evicts LRU (the chain HEAD) which cascades through its own
        # descendants -> nothing survives, nothing promotable, and no
        # orphaned host entries linger
        dev, host_run = a.lookup_tiered(digests)
        assert dev == []
        assert len(host_run) == want_host
        assert a.host_blocks_used == want_host
        if want_host == 0:
            assert a.host_head_digests() == frozenset()
        assert a.cache_demotions + a.cache_drops >= 3


def test_int8_scales_round_trip_demote_promote(setup, rng):
    """int8 KV blocks demote WITH their quantization scales and promote
    back bit-exactly: cold -> pressure (demotes the parked chain) ->
    warm re-admit of the same prompt must produce bit-identical greedy
    tokens from the promoted int8 payloads."""
    cfg, model, params = setup
    prompt = rng.integers(0, cfg.vocab_size, 256).astype(np.int32)
    pressure = rng.integers(0, cfg.vocab_size, 320).astype(np.int32)
    # pool: pressure (21 blocks worst) + slack 2; the cold chain (16
    # blocks) cannot stay device-resident through the pressure serve
    eng = Engine(0, model, params, max_slots=2, max_seq=512,
                 token_budget=23 * 16, block_size=16,
                 prefill_token_budget=64, attn_backend="dense",
                 kv_dtype="int8", host_kv_budget=512)
    cold = ServeRequest(0, prompt.copy(), 6)
    _drain(eng, cold)
    d0 = eng.cache_demotions
    _drain(eng, ServeRequest(1, pressure.copy(), 6))
    assert eng.cache_demotions > d0, "pressure prompt demoted nothing"
    p0 = eng.cache_promotions
    warm = ServeRequest(2, prompt.copy(), 6)
    _drain(eng, warm)
    assert eng.cache_promotions > p0, "warm re-admit promoted nothing"
    assert warm.cached_tokens > 0
    assert warm.generated == cold.generated, \
        "int8 demote->promote round trip changed tokens"
    eng.check_drained()
