"""Tensor-parallel serving (DESIGN.md §Sharded serving, ISSUE 9).

Shard-count invariance on a CPU-forced 2-device mesh (conftest sets
``--xla_force_host_platform_device_count=2``): a ``tp=2`` engine shards
the paged KV pool and weights over KV heads via ``shard_map`` but must
be a pure implementation detail — greedy tokens bit-identical to
``tp=1`` (whose dense backend is the oracle), prefix-cache sharing,
park/recompute preemption resume, and cross-TP migration all
unchanged, while resident KV capacity doubles at equal PER-DEVICE pool
budget and the one-d2h / one-attention-launch-per-mixed-step
disciplines survive the mesh.
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.serving.engine import Engine
from repro.serving import engine as engine_mod
from repro.serving.request import ServeRequest, State

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="tensor-parallel tests need >= 2 (virtual) devices")


@pytest.fixture(scope="module")
def setup():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, tp, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", 96)
    kw.setdefault("token_budget", 256)
    kw.setdefault("attn_backend", "dense")
    return Engine(tp, model, params, tp=tp, **kw)


def _mkreqs(vocab, shapes, seed=0, **attrs):
    rng = np.random.default_rng(seed)
    out = []
    for i, (p, n) in enumerate(shapes):
        r = ServeRequest(i, rng.integers(0, vocab, p).astype(np.int32), n)
        r.arrival_step = i
        for k, v in attrs.items():
            setattr(r, k, v)
        out.append(r)
    return out


def _drive(eng, reqs, max_steps=400):
    for r in reqs:
        eng.submit(r)
    for _ in range(max_steps):
        eng.step()
        if all(r.state is State.FINISHED for r in reqs):
            break
    assert all(r.state is State.FINISHED for r in reqs)
    return [list(r.generated) for r in reqs]


SHAPES = [(9, 10), (21, 10), (13, 8), (6, 10)]


# --------------------------------------------------------------------------
# Greedy parity + capacity (the ISSUE-9 acceptance pair)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["dense", "fused"])
def test_tp2_greedy_tokens_bit_identical_to_tp1(setup, backend):
    """tp=2 emits bit-identical greedy tokens to tp=1 — and tp=1/dense
    IS the dense oracle, so both backends are transitively checked."""
    cfg, model, params = setup
    ref = _drive(_engine(model, params, 1),
                 _mkreqs(cfg.vocab_size, SHAPES))
    got = _drive(_engine(model, params, 2, attn_backend=backend),
                 _mkreqs(cfg.vocab_size, SHAPES))
    assert got == ref


def test_tp2_doubles_resident_kv_at_equal_per_device_budget(setup):
    """``token_budget`` is PER-DEVICE: each shard holds Hkv/tp heads of
    every block, so a tp=2 engine owns 2x the blocks (and resident
    tokens) at the same per-device pool bytes."""
    cfg, model, params = setup
    e1 = _engine(model, params, 1)
    e2 = _engine(model, params, 2)
    assert e2.num_blocks == 2 * e1.num_blocks
    assert e2.token_budget == 2 * e1.token_budget
    assert e2.free_tokens() == 2 * e1.free_tokens()
    # per-device bytes really are equal: the sharded pool splits the
    # kv-head axis, so each shard stores half of 2x the blocks
    leaf1 = jax.tree.leaves(e1.cache)[0]
    leaf2 = jax.tree.leaves(e2.cache)[0]
    assert leaf1.shape[1] == e1.num_blocks + 1           # +1 garbage block
    assert leaf2.shape[1] == e2.num_blocks + 1           # 2x global blocks
    shard = next(iter(leaf2.addressable_shards)).data
    assert shard.size == leaf2.size // 2                 # per-device half


def test_tp2_one_attn_call_one_d2h_per_mixed_step(setup, monkeypatch):
    """The fused one-launch and one-sync contracts hold under shard_map:
    a tp=2 mixed step (long prompt chunking beside live decodes) makes
    exactly ONE attention-bearing device call and ONE d2h."""
    cfg, model, params = setup
    d2h_calls = []
    real = engine_mod.d2h
    monkeypatch.setattr(engine_mod, "d2h",
                        lambda x: d2h_calls.append(1) or real(x))
    eng = _engine(model, params, 2, attn_backend="fused",
                  prefill_token_budget=8)
    short = _mkreqs(cfg.vocab_size, [(5, 10), (11, 10)], seed=3)
    for r in short:
        eng.submit(r)
    while any(r.prefilling or r.state is State.WAITING for r in short):
        eng.step()
    rng = np.random.default_rng(4)
    long_req = ServeRequest(9, rng.integers(0, cfg.vocab_size, 24)
                            .astype(np.int32), 2)
    eng.submit(long_req)
    attn, sync = [], []
    while long_req.prefilling or long_req.first_token_step is None:
        d2h_calls.clear()
        c0 = engine_mod.ATTN_CALLS
        eng.step()
        attn.append(engine_mod.ATTN_CALLS - c0)
        sync.append(len(d2h_calls))
    assert attn and max(attn) == 1, attn
    assert all(s == 1 for s in sync), sync
    while any(not r.done for r in short + [long_req]):
        eng.step()


# --------------------------------------------------------------------------
# Prefix cache, preemption, migration — all invariant under sharding
# --------------------------------------------------------------------------
def test_tp2_prefix_cache_sharing_parity(setup):
    """Shared-prefix admission (refcounted blocks, cached_tokens) works
    identically on the sharded pool: the warm request hits the cache on
    both engines and tokens stay bit-identical."""
    cfg, model, params = setup
    rng = np.random.default_rng(5)
    prefix = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
    tails = [rng.integers(0, cfg.vocab_size, t).astype(np.int32)
             for t in (7, 5)]
    outs, hits = {}, {}
    for tp in (1, 2):
        eng = _engine(model, params, tp, prefill_token_budget=16)
        reqs = [ServeRequest(i, np.concatenate([prefix, t]), 8)
                for i, t in enumerate(tails)]
        eng.submit(reqs[0])
        while reqs[0].state is not State.FINISHED:    # publishes the prefix
            eng.step()
        eng.submit(reqs[1])
        while reqs[1].state is not State.FINISHED:
            eng.step()
        outs[tp] = [list(r.generated) for r in reqs]
        hits[tp] = reqs[1].cached_tokens
        eng.allocator.check_invariants()
    assert outs[2] == outs[1]
    assert hits[2] == hits[1] > 0, "warm request must share the prefix"


@pytest.mark.parametrize("mode", ["_preempt_park", "_preempt_recompute"])
def test_tp2_preempt_resume_bit_identical(setup, mode):
    """Park and drop-and-recompute preemption resume bit-identically on
    the sharded engine (the allocator and resume machinery never see the
    mesh; recompute replays through the sharded chunked prefill)."""
    cfg, model, params = setup
    shapes = SHAPES[:3]
    ref = _drive(_engine(model, params, 1, preemption=False),
                 _mkreqs(cfg.vocab_size, shapes))
    eng = _engine(model, params, 2, preemption=True)
    reqs = _mkreqs(cfg.vocab_size, shapes)
    for r in reqs:
        eng.submit(r)
    for _ in range(6):
        eng.step()
    slot = next(s for s, r in enumerate(eng.slots)
                if r is not None and r.generated and not r.prefilling)
    getattr(eng, mode)(slot)
    eng.allocator.check_invariants()
    for _ in range(400):
        eng.step()
        if all(r.state is State.FINISHED for r in reqs):
            break
    assert [list(r.generated) for r in reqs] == ref
    assert eng.preemptions == 1 and eng.resumes == 1


def test_migration_round_trip_between_different_tp(setup):
    """Live migration tp=1 -> tp=2 -> tp=1: the wire format is the same
    contiguous unsharded piece (export gathers shards to host, import
    re-pins under the receiver's sharding), so engines of different TP
    interoperate and the decode continues bit-identically."""
    cfg, model, params = setup
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, 13).astype(np.int32)
    r = ServeRequest(0, prompt.copy(), 14)
    ref = ServeRequest(9, prompt.copy(), 14)
    a = _engine(model, params, 1, max_slots=2)
    b = _engine(model, params, 2, max_slots=2)
    ref_eng = _engine(model, params, 1, max_slots=2)
    a.submit(r)
    ref_eng.submit(ref)
    for _ in range(4):
        a.step()
        ref_eng.step()
    src_slot = r.slot
    req, piece, nbytes = a.export_slot(src_slot)
    assert nbytes > 0
    assert b.import_request(req, piece)           # tp=1 piece -> tp=2 pool
    a.evict_slot(src_slot)
    a.allocator.check_invariants()
    for _ in range(4):
        b.step()
        ref_eng.step()
    src_slot = r.slot
    req, piece, _ = b.export_slot(src_slot)       # tp=2 piece -> tp=1 pool
    assert a.import_request(req, piece)
    b.evict_slot(src_slot)
    b.allocator.check_invariants()
    while r.state is not State.FINISHED:
        a.step()
    while ref.state is not State.FINISHED:
        ref_eng.step()
    assert r.generated == ref.generated
    assert set(r.tokens_by_engine) >= {a.id, b.id}


# --------------------------------------------------------------------------
# Sharded weight init and the engine's logits probe
# --------------------------------------------------------------------------
def test_init_params_tp2_born_sharded_same_values(setup):
    """``init_params(tp=2)`` creates every weight straight into its
    serving sharding (no device ever holds a sharded weight whole) with
    the same values as the single-device init."""
    from jax.sharding import NamedSharding
    from repro.launch.serve import init_params
    from repro.launch.shardings import serving_param_spec_tree
    cfg, model, _ = setup
    sharded = init_params(model, 0, tp=2)
    single = init_params(model, 0)
    specs = serving_param_spec_tree(single, 2)
    for leaf, spec, ref in zip(jax.tree.leaves(sharded),
                               jax.tree.leaves(specs, is_leaf=lambda x:
                                               isinstance(x, P)),
                               jax.tree.leaves(single)):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.spec == spec
        if any(spec):
            shard = next(iter(leaf.addressable_shards)).data
            assert shard.size == leaf.size // 2
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))


@pytest.mark.parametrize("backend", ["dense", "fused"])
def test_prompt_logits_probe_tp2_matches_tp1(setup, backend):
    """``Engine.prompt_logits`` runs a prompt through the engine's own
    chunked-prefill path (several chunks here) without touching request
    state: tp=2 agrees with tp=1, its argmax is the first token the
    engine then serves, and the pool and allocator are unchanged."""
    cfg, model, params = setup
    e1 = _engine(model, params, 1, prefill_token_budget=8)
    e2 = _engine(model, params, 2, prefill_token_budget=8,
                 attn_backend=backend)
    prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, 21)
    free = e2.free_tokens()
    l1, l2 = e1.prompt_logits(prompt), e2.prompt_logits(prompt)
    np.testing.assert_allclose(l2, l1, atol=2e-5, rtol=2e-5)
    assert e2.free_tokens() == free
    assert float(np.abs(np.asarray(jax.tree.leaves(e2.cache)[0])).max()) == 0
    r = ServeRequest(0, prompt.astype(np.int32), 2)
    _drive(e2, [r])
    assert r.generated[0] == int(np.argmax(l2))


def test_build_server_full_stack_tp2(setup):
    """``launch.serve.build_server`` (the launcher's and chip smoke's
    factory) builds a tp=2 cascade cluster whose weights are sharded at
    birth, and serves a mixed-length trace to completion."""
    from repro.launch.serve import build_server
    from repro.serving.server import ServerConfig
    cfg, model, params = setup
    srv = build_server(cfg, ServerConfig(policy="cascade"), engines=1, tp=2,
                       max_seq=96, max_slots=4, attn_backend="fused")
    eng = srv.engines[0]
    assert eng.tp == 2 and eng.fused_mixed
    assert any(next(iter(w.addressable_shards)).data.size == w.size // 2
               for w in jax.tree.leaves(eng.params))
    reqs = _mkreqs(cfg.vocab_size, SHAPES)
    srv.run(reqs, max_steps=400)
    assert len(srv.finished) == len(reqs)
    assert all(len(r.generated) == r.max_new_tokens for r in srv.finished)
