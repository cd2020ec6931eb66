"""Single-instance JAX inference engine: block-granular paged KV cache +
continuous batching (the vLLM-role component of DESIGN §3; layouts and
invariants in DESIGN.md).

Two cache layouts behind one scheduling surface:

  * **paged** (default for full-attention decoder families): a global block
    pool with leaves ``[L, num_blocks, Hkv, block_size, Dp]`` plus a
    per-request block table, managed by ``BlockAllocator``. Admission gates
    on worst-case *block reservations* (``ceil(min(prompt+max_new,
    max_seq)/BS)``), physical blocks are allocated incrementally as the
    sequence grows, and a 16-token request pins 16 tokens of cache — not a
    ``max_seq`` slab. ``free_tokens()`` can never go negative.
  * **monolithic** fallback (ssm/rwkv recurrent state, sliding-window ring
    buffers): preallocated ``[L, slots, S_max, ...]`` slab, one slot per
    request, with the same reservation-based admission accounting.

Every ``step()`` is one **mixed** continuous-batching iteration (DESIGN.md
§Chunked prefill): pack up to ``prefill_token_budget`` prompt-chunk tokens
(resuming partial prompts oldest-first, then admitting FCFS) alongside the
full decode batch, then advance every fully-prefilled request by one
token with a single batched decode. Chunk K/V is scattered into freshly
allocated pool blocks, so partial prompts live in the same pool as decode
state; a long prompt therefore never freezes decoding for more than one
iteration (the §2.1 head-of-line block this engine used to have —
``chunked_prefill=False`` keeps that whole-prompt baseline). Migration
exports a request's KV trimmed to its actual written length (paged: a
gather of its blocks; mid-prefill: the ``ctx_done`` rows, resumed on the
receiver) — the wire format is the same contiguous ``[L, 1, length, ...]``
piece for both layouts, so mixed clusters interoperate (DESIGN.md
§Migration wire format).

**Device-resident decode hot loop** (paged engines, the default —
DESIGN.md §Decode hot path): block tables, slot lengths, and last tokens
live as device arrays (pow2-capped width growth), sampling is a fused
on-device argmax over the whole ``max_slots``-wide batch, and every
``step()`` performs exactly ONE device→host transfer — the sampled
tokens, routed through :func:`d2h` so tests can count it. ``step(burst=n)``
fuses up to ``n`` consecutive iterations into one ``lax.scan``
micro-batch (the fusion never crosses a count/capacity finish boundary,
so continuous-batching admission is not delayed). Prompt prefills are
padded to pow2 buckets so compiles stay O(log max_seq), not O(distinct
prompt lengths). ``device_resident=False`` keeps the original host-driven
loop — the bit-parity reference.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import weakref
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
# host spans of a step's phases (DESIGN.md §Tracing)
from jax.profiler import TraceAnnotation as _span
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.migration import (gather_kv_blocks, kv_bytes,
                                  scatter_kv_blocks)
from repro.kernels.cost import pow2_bucket
from repro.kernels.mixed_attention import MAX_WORK_ITEMS
from repro.launch.mesh import make_tp_mesh
from repro.launch.shardings import (piece_spec_tree, pool_spec_tree,
                                    serving_param_spec_tree)
from repro.models.attention import (QuantKVCache, blocks_to_piece,
                                    dequantize_piece, piece_to_blocks,
                                    quantize_piece, resolve_paged_backend)
from repro.models.model import Model, build_model
from repro.sched.policy import park_or_recompute
from repro.sched.slo import (aging_promotion, insert_sorted, priority_of,
                             queue_key, tpot_hopeless)
from repro.serving.block_pool import (BlockAllocator, blocks_for, chain_hash,
                                      prompt_chain)
from repro.serving.request import ServeRequest, State

DEFAULT_BLOCK_SIZE = 16
# Per-iteration prompt-chunk token budget of the mixed scheduler
# (DESIGN.md §Chunked prefill): every step packs up to this many prompt
# tokens (oldest request first) alongside the full decode batch, so a
# long prompt can never freeze decoding for more than one iteration.
DEFAULT_PREFILL_BUDGET = 256

# Running count of device->host synchronizations performed by all engines
# in this process (bench_decode_hotloop reads it; tests monkeypatch d2h).
D2H_CALLS = 0

# Weakrefs to every engine ever constructed in this process. The test
# suite's drain-leak fixture walks this after each test and asserts no
# engine is left holding reservations or parked requests (crashed
# engines are skipped via their `_faulted` flag).
_LIVE_ENGINES: List["weakref.ref"] = []


def d2h(x) -> np.ndarray:
    """The engine's ONLY device→host synchronization point. Every token
    that reaches Python crosses here, so `D2H_CALLS` (and a test shim
    monkeypatching this function) measures host round-trips exactly."""
    global D2H_CALLS
    D2H_CALLS += 1
    return np.asarray(x)


# Running count of attention-bearing device calls (jitted forwards that
# execute attention kernels) issued by all engines in this process. Launch
# counters INSIDE a jitted function only tick at trace time, so the
# one-launch-per-mixed-step contract is asserted here instead: every such
# forward is routed through :func:`attn_call` (the launch-count twin of
# :func:`d2h`), and a fused mixed step makes exactly ONE call where the
# separate-kernel path makes two (chunk batch + decode burst).
ATTN_CALLS = 0


def attn_call(fn, *args, **kwargs):
    """Issue one attention-bearing device call (and count it)."""
    global ATTN_CALLS
    ATTN_CALLS += 1
    return fn(*args, **kwargs)


_next_pow2 = pow2_bucket     # ONE bucketing policy (kernels/cost.py)


def _pow2_floor(n: int) -> int:
    assert n >= 1
    return 1 << (n.bit_length() - 1)


@dataclasses.dataclass
class _Parked:
    """A park-preempted request: off its batch slot, KV blocks (and the
    covering reservation) intact. ``_unpark`` restores it into any free
    slot with bit-identical continuation (DESIGN.md §SLO scheduling)."""
    req: ServeRequest
    table: List[int]
    shared: int          # shared prefix-head blocks (released owned=False)
    rblocks: int         # reservation units the request still holds
    slot_len: int


class Engine:
    def __init__(self, engine_id: int, model: Model, params, *,
                 max_slots: int = 8, max_seq: int = 512,
                 token_budget: Optional[int] = None,
                 paged: Optional[bool] = None,
                 block_size: int = DEFAULT_BLOCK_SIZE,
                 device_resident: Optional[bool] = None,
                 attn_backend: Optional[str] = None,
                 prefill_token_budget: Optional[int] = None,
                 chunked_prefill: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 kv_dtype: str = "bf16",
                 host_kv_budget: int = 0,
                 preemption: Optional[bool] = None,
                 slo_time_scale: float = 1.0,
                 tp: int = 1):
        assert model.cfg.family in ("dense", "moe", "vlm", "ssm"), \
            "engine supports decoder-only families"
        assert kv_dtype in ("bf16", "int8"), kv_dtype
        # Serving tensor parallelism (DESIGN.md §Sharded serving): tp > 1
        # rebuilds the model with the manual-collective tp_axis, pins
        # params + pool to a 1-D 'model' mesh over the first ``tp`` local
        # devices, and runs every attention-bearing jit through shard_map.
        # Only the pool's kv-head axis is sharded — the allocator, prefix
        # index, block tables and migration wire format never see the mesh.
        self.tp = int(tp)
        if self.tp > 1:
            cfg = model.cfg
            assert model.supports_paged and paged is not False, \
                "tensor-parallel serving needs the paged block pool"
            assert device_resident is not False, \
                "tensor-parallel serving needs the device-resident loop"
            assert cfg.num_kv_heads % self.tp == 0, \
                f"kv heads {cfg.num_kv_heads} not divisible by tp={self.tp}"
            assert cfg.num_heads % self.tp == 0, \
                f"heads {cfg.num_heads} not divisible by tp={self.tp}"
            assert cfg.vocab_size % self.tp == 0, \
                f"vocab {cfg.vocab_size} not divisible by tp={self.tp}"
            assert cfg.d_ff % self.tp == 0, \
                f"d_ff {cfg.d_ff} not divisible by tp={self.tp}"
            model = build_model(dataclasses.replace(cfg, tp_axis="model"))
            self.mesh = make_tp_mesh(self.tp)
            self._pspec = serving_param_spec_tree(params, self.tp)
            params = jax.device_put(params, jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self._pspec))
        self.id = engine_id
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.token_budget = token_budget or max_slots * max_seq
        self.paged = model.supports_paged if paged is None else paged
        if self.paged:
            assert model.supports_paged, \
                f"{model.cfg.name} ({model.cfg.family}) has no paged path"
            self.block_size = block_size
            # ``token_budget`` is the PER-DEVICE pool budget: each shard
            # holds Hkv/tp heads of every block, so a tp-engine owns tp×
            # the blocks (and resident tokens) at equal per-device bytes.
            self.num_blocks = (self.token_budget * self.tp) // block_size
            assert self.num_blocks > 0, \
                f"token_budget {self.token_budget} < one block ({block_size})"
            # capacity is block-granular: tokens that don't fill a block
            # can't back any request (mirrors sim.Instance)
            self.token_budget = self.num_blocks * block_size
            # host-RAM KV tier (DESIGN.md §Multi-tier KV): reclaimed
            # cached chains demote to a capacity-bounded host store
            # instead of dying; 0 keeps the drop-on-reclaim behavior
            # bit-exactly
            self.host_kv_budget = int(host_kv_budget or 0)
            self.allocator = BlockAllocator(
                self.num_blocks, block_size,
                host_blocks=self.host_kv_budget // block_size)
            if self.allocator.host_tier_enabled:
                self.allocator.set_demote_fetch(self._demote_snapshot)
            # +1 garbage block (id num_blocks, never allocated): dead batch
            # slots and padded table rows write/read there by construction,
            # so the fixed-shape device loop cannot corrupt live blocks
            self.garbage_block = self.num_blocks
            self.kv_dtype = kv_dtype
            # int8 pools halve KV bytes, so the same token_budget holds
            # nearly 2x the blocks (DESIGN.md §Quantized KV blocks);
            # quantized rows are only readable by the fused kernel and the
            # dense gather
            init = functools.partial(
                model.init_paged_cache, self.num_blocks + 1, block_size,
                **({"kv_dtype": kv_dtype} if kv_dtype == "int8" else {}))
            if self.tp > 1:
                # made in its sharding: no device ever holds more than its
                # Hkv/tp heads of the pool (the whole pool is tp x what
                # one device can spare beside its weights)
                self._pool_spec = pool_spec_tree(jax.eval_shape(init))
                self.cache = jax.jit(init, out_shardings=jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s),
                    self._pool_spec))()
            else:
                self.cache = init()
            self.block_tables: List[List[int]] = [[] for _ in range(max_slots)]
            self._bytes_per_block = kv_bytes(self.cache) / (self.num_blocks + 1)
            self.device_resident = (device_resident
                                    if device_resident is not None else True)
            self.attn_backend, self.attn_interpret = \
                resolve_paged_backend(attn_backend)
            if kv_dtype == "int8":
                assert self.attn_backend in ("fused", "dense"), \
                    "int8 KV needs the 'fused' or 'dense' attention backend"
            if self.device_resident:
                assert model.prefill_bucketed is not None, \
                    "device-resident loop needs Model.prefill_bucketed"
                self._nbt_cap = 1               # device table width (pow2)
                self._dev_bt = jnp.full((max_slots, 1), self.garbage_block,
                                        jnp.int32)
                self._dev_len = jnp.zeros((max_slots,), jnp.int32)
                self._dev_tok = jnp.zeros((max_slots,), jnp.int32)
                self._burst_fns: Dict[Tuple[int, int], Callable] = {}
                if self.tp > 1:
                    # bucketed prefill returns a contiguous KV piece
                    # [L, B, P, Hkv, Dh] — kv heads sharded like the pool
                    self._prefill_bucketed = jax.jit(self._smap(
                        model.prefill_bucketed, (self._pspec, P(), P()),
                        (P(), P(None, None, None, "model", None))))
                else:
                    self._prefill_bucketed = jax.jit(model.prefill_bucketed)
                self._pending_first: List[Tuple[ServeRequest, jnp.ndarray]] = []
            else:
                # the host loop honors the backend too (attn_num_work
                # stays None -> the flat wrapper's B·NBT worst case)
                self._decode_paged = jax.jit(functools.partial(
                    model.decode_step_paged,
                    attn_backend=self.attn_backend,
                    attn_interpret=self.attn_interpret))
        else:
            assert kv_dtype == "bf16", \
                "quantized KV needs the paged block pool"
            self.block_size = 0
            self.device_resident = False
            self.cache = model.init_cache(max_slots, max_seq)
            self._bytes_per_slot = kv_bytes(self.cache) / max_slots
            self._decode = jax.jit(model.decode_step)
        # Chunked paged prefill (DESIGN.md §Chunked prefill): on by default
        # wherever the model supports it; chunked_prefill=False keeps the
        # whole-prompt path (the monolithic-prefill baseline).
        chunk_ok = self.paged and model.prefill_chunk is not None
        self.chunked_prefill = (chunk_ok if chunked_prefill is None
                                else chunked_prefill)
        self.prefill_token_budget = (prefill_token_budget
                                     or DEFAULT_PREFILL_BUDGET)
        self._prefill_order: List[int] = []   # slots mid-prefill, oldest 1st
        if self.chunked_prefill:
            assert chunk_ok, \
                f"{model.cfg.name}: chunked prefill needs a paged engine " \
                "and Model.prefill_chunk"
            ck = functools.partial(model.prefill_chunk,
                                   attn_backend=self.attn_backend,
                                   attn_interpret=self.attn_interpret)
            if self.tp > 1:
                ck = self._smap(ck, (self._pspec, self._pool_spec,
                                     P(), P(), P(), P()),
                                (P(), self._pool_spec))
            self._prefill_chunk = jax.jit(ck)
        # Fused mixed iterations (DESIGN.md §Fused mixed-iteration
        # attention): when the backend is "fused" and the model has a
        # mixed_step, the device loop runs the decode batch AND the step's
        # prompt chunks through ONE attention-bearing device call (one
        # kernel launch per layer). Otherwise mixed steps stay two calls —
        # the bit-parity separate-kernel reference.
        self.fused_mixed = bool(
            self.chunked_prefill and self.device_resident
            and self.attn_backend == "fused"
            and getattr(model, "mixed_step", None) is not None)
        if self.fused_mixed:
            self._mixed = self._mixed_fn()
        # Chunks a step plans at most. On "fused" the kernel holds (decode
        # rows + chunks) x table width work items in SMEM; a chunk's table
        # covers its padded rows, at most one chunk bucket past max_seq,
        # so the widest call caps the chunks.
        self._max_chunks = max_slots
        if self.paged:
            self._max_chunk_nbt = blocks_for(
                max_seq + _next_pow2(self.prefill_token_budget),
                self.block_size)
        if self.paged and self.attn_backend == "fused":
            nbt = (self._max_chunk_nbt if self.chunked_prefill
                   else blocks_for(max_seq, self.block_size))
            fit = MAX_WORK_ITEMS // nbt - max_slots
            if fit < int(self.chunked_prefill):
                raise ValueError(
                    f"fused attention: {max_slots} slots x {nbt} table "
                    f"columns leave no room for a prefill chunk in the "
                    f"kernel's {MAX_WORK_ITEMS}-item work list; lower "
                    f"max_slots or max_seq")
            self._max_chunks = min(max_slots, fit)
        # Refcounted prefix cache (DESIGN.md §Prefix cache): admission
        # shares already-resident full prompt blocks and starts chunked
        # prefill at ctx_done = cached_tokens, so a warm request skips the
        # cached blocks' prefill work entirely. Needs the chunked paged
        # path (warm starts resume mid-prompt); prefix_cache=False is the
        # bit-parity legacy path.
        self.prefix_cache = (self.chunked_prefill if prefix_cache is None
                             else bool(prefix_cache and self.chunked_prefill))
        if self.paged:
            self._slot_rblocks = [0] * max_slots   # reserved blocks per slot
            self._slot_shared = [0] * max_slots    # shared table-head blocks
        self.slot_len = np.zeros(max_slots, np.int32)       # tokens in cache
        self.slots: List[Optional[ServeRequest]] = [None] * max_slots
        self.slot_reserved = np.zeros(max_slots, np.int64)  # worst-case tokens
        self.waiting: Deque[ServeRequest] = deque()
        # SLO-tiered preemptive scheduling (DESIGN.md §SLO scheduling &
        # preemption): off by default on direct construction — the
        # bit-parity FCFS legacy path. When on, the waiting queue is kept
        # sorted by repro.sched.slo.queue_key and a blocked higher-class
        # request may park (slot shortage) or recompute-preempt (memory
        # shortage) the lowest-class resident decode.
        self.slo_sched = bool(preemption)
        self.slo_time_scale = float(slo_time_scale)
        self.parked: List[_Parked] = []
        self._seq = 0                # submission tie-break for queue_key
        self.preemptions = 0         # victim pauses (park + recompute)
        self.preempt_recomputes = 0  # victims whose KV was dropped
        self.resumes = 0             # park restores + recompute completions
        # TPOT-deadline admission (DESIGN.md §SLO scheduling): resumed
        # decodes whose TPOT is already unrecoverable never preempt
        # healthy traffic — counted here (once per request) against
        # attainment instead
        self.tpot_skipped = 0
        self._tpot_hopeless_ids: set = set()
        self.steps = 0
        self.tokens_out = 0
        # fused device calls that advanced decodes AND prompt chunks
        self.mixed_steps = 0
        self.peak_kv_bytes = 0.0
        # prefill cost counters (bench_prefix_cache reads them): block-work
        # actually run by prefill (Σ per chunk ceil((ctx+clen)/BS) — the
        # grid-step mirror) vs. prompt tokens served straight from the
        # prefix index. A warm identical prompt shows up as a collapsed
        # prefill_work_blocks and a matching cached_prompt_tokens_total.
        self.prefill_work_blocks = 0
        self.prefill_tokens_done = 0
        self.cached_prompt_tokens_total = 0
        # multi-tier KV counters (DESIGN.md §Multi-tier KV): blocks
        # promoted from the host tier back onto device at admission
        self.promoted_blocks_total = 0
        # work-list accounting of the device loop's launches (the
        # ``engine.launch`` span's ``items``/``real_items``, summed):
        # work items launched (the fused kernel's real items; the other
        # backends' pow2 bucket, x the horizon) and those holding a real
        # KV block
        self.work_items = 0
        self.real_work_items = 0
        # operand bytes, on each chip, of the collectives in the launched
        # programs (the ``engine.launch`` span's ``collective_bytes``,
        # summed; 0 at tp = 1, see ``_collective_bytes``)
        self.collective_bytes = 0
        # first admissions of requests a server submitted, and the sum of
        # their queue waits (host clock, submit to admission)
        self.admitted_total = 0
        self.queue_wait_s_total = 0.0
        self._prefill = jax.jit(model.prefill,
                                static_argnames=("cache_len",))
        _LIVE_ENGINES.append(weakref.ref(self))

    # ---- serving tensor parallelism (DESIGN.md §Sharded serving) ----------
    def _smap(self, fn, in_specs, out_specs):
        """shard_map a forward over this engine's 1-D 'model' mesh.
        ``check_vma=False``: block tables / work lists are replicated by
        construction and the psum sites live inside the model."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _localize_piece(self, piece):
        """Adopt a migration piece gathered on ANOTHER engine's mesh: pull
        it to host and re-place it under this engine's sharding (plain
        device arrays for tp=1). Same-mesh pieces pass through untouched.
        The host copy is migration traffic — accounted by the cluster's
        byte ledger, not the step's d2h discipline."""
        leaves = jax.tree_util.tree_leaves(piece)
        if not leaves or not hasattr(leaves[0], "sharding"):
            return piece
        here = jax.tree_util.tree_leaves(self.cache)[0].sharding
        if leaves[0].sharding.device_set == here.device_set:
            return piece
        host = jax.tree.map(np.asarray, piece)
        if self.tp > 1:
            return jax.device_put(host, jax.tree.map(
                lambda s: NamedSharding(self.mesh, s),
                piece_spec_tree(piece)))
        return jax.tree.map(jnp.asarray, host)

    def _collective_bytes(self, rows: int, sampled: int) -> Optional[int]:
        """Operand bytes, on each chip, of the collectives in a program
        that runs ``rows`` token rows (padding included) through the stack
        and gives logits for ``sampled`` of them: every row passes the
        embedding's psum and, in every layer, the psums after ``wo`` and
        ``w_down``, each over ``d_model``; every sampled row passes the
        logits all-gather, whose operand is the chip's ``vocab / tp``
        columns. 0 at tp = 1. ``None`` where the layers carry experts,
        whose FFN psum has another shape, so nothing is counted."""
        if self.tp == 1:
            return 0
        cfg = self.model.cfg
        if cfg.num_experts:
            return None
        width = jnp.dtype(cfg.dtype).itemsize
        return width * ((2 * cfg.num_layers + 1) * rows * cfg.d_model
                        + sampled * (cfg.vocab_size // self.tp))

    # ---- drain-time leak check (DESIGN.md §Fault tolerance) ---------------
    def check_drained(self, strict: bool = True) -> None:
        """Assert this engine holds no request state. ``strict`` also
        requires the queues to be empty (a post-run server drain);
        non-strict only checks that ALLOCATOR state matches the resident
        requests — the invariant conftest runs after every test, where
        engines may legitimately still hold live requests."""
        if strict:
            assert all(r is None for r in self.slots), \
                f"engine {self.id}: undrained slots"
            assert not self.waiting, f"engine {self.id}: undrained queue"
            assert not self.parked, f"engine {self.id}: undrained parked"
            assert not self._prefill_order, \
                f"engine {self.id}: dangling prefill order"
        if self.paged:
            self.allocator.check_invariants()
            if strict and not any(self.slots) and not self.parked:
                self.allocator.check_drained()
        elif strict:
            assert int(self.slot_reserved.sum()) == 0, \
                f"engine {self.id}: leaked slot reservations"
            assert int(self.slot_len.sum()) == 0, \
                f"engine {self.id}: leaked slot lengths"

    def shutdown(self) -> None:
        """End-of-life check + release: asserts the engine drained clean,
        then drops its device buffers."""
        self.check_drained(strict=True)
        self.cache = None
        if self.paged:
            self.block_tables = [[] for _ in self.block_tables]

    # ---- load views --------------------------------------------------------
    def active(self) -> List[ServeRequest]:
        return [r for r in self.slots if r is not None]

    def used_tokens(self) -> int:
        """Tokens of cache memory actually pinned by running requests.
        Paged: allocated blocks × block size; monolithic: live cache rows.
        (Waiting prompts hold no cache — they are reported by
        ``queued_tokens``/``load`` instead, so admission and the free
        budget agree on one definition.)"""
        if self.paged:
            return self.allocator.allocated_tokens()
        return int(self.slot_len.sum())

    def reserved_tokens(self) -> int:
        """Worst-case committed footprint of all admitted requests —
        what admission gates on (never exceeds the budget)."""
        if self.paged:
            return self.allocator.reserved_blocks * self.block_size
        return int(self.slot_reserved.sum())

    def queued_tokens(self) -> int:
        """UN-PREFILLED, UNCACHED prompt tokens: whole waiting prompts
        (minus their prefix-cache hit, estimated at submit) plus the
        not-yet-written remainder of requests mid-chunked-prefill. The
        written part of a partial prompt is already pinned cache and shows
        up in ``used_tokens`` — one token never counts twice, and a warm
        30K prompt whose first 28K tokens are resident queues as the
        short request it effectively is (DESIGN.md §Prefix cache)."""
        q = sum(r.prefill_target_len - r.cached_tokens for r in self.waiting)
        q += sum(r.prefill_target_len - r.ctx_done
                 for r in self.active() if r.prefilling)
        return int(q)

    def free_tokens(self) -> int:
        """Unpinned cache budget; the admission invariant keeps this >= 0."""
        return self.token_budget - self.used_tokens()

    def load(self) -> float:
        """Scheduling pressure: pinned cache + queued prompt tokens."""
        return float(self.used_tokens() + self.queued_tokens())

    def kv_bytes_pinned(self) -> float:
        """Cache bytes pinned right now (paged: allocated blocks;
        monolithic: occupied max_seq slabs)."""
        if self.paged:
            return self.allocator.allocated_blocks * self._bytes_per_block
        return sum(1 for r in self.slots if r is not None) \
            * self._bytes_per_slot

    def has_idle_slot(self) -> bool:
        return any(r is None for r in self.slots)

    def request_view(self) -> List[Tuple[float, float]]:
        return [(float(len(r.prompt)), float(r.length)) for r in self.active()]

    # ---- prefix cache (DESIGN.md §Prefix cache) ------------------------------
    def _prompt_digests(self, prompt) -> List[int]:
        """Chain digests of the prompt's full blocks, capped at
        ``(len-1)//BS`` so even a fully-cached identical prompt still
        prefill-computes >= 1 token (the first output token needs the last
        position's logits)."""
        return prompt_chain(prompt, self.block_size,
                            limit=(len(prompt) - 1) // self.block_size)

    def _req_digests(self, req: ServeRequest) -> List[int]:
        """Per-request digest memo: the prompt is immutable, so its sha1
        chain is computed ONCE per block size — not per hint probe, per
        submit, and per admission re-check of the waiting-queue head."""
        cache = req.prefix_digests_memo
        if cache is None or cache[0] != self.block_size:
            cache = (self.block_size, self._prompt_digests(req.prompt))
            req.prefix_digests_memo = cache
        return cache[1]

    def _cached_chain(self, req: ServeRequest) -> List[int]:
        """Longest resident block chain for this prompt ([] when the
        cache is off or cold)."""
        if not self.prefix_cache:
            return []
        return self.allocator.lookup(self._req_digests(req))

    def _tiered_chain(self, req: ServeRequest):
        """(device block ids, host digest continuation) — the two-tier
        chain hit admission consumes: device blocks are shared for free,
        host digests are promoted at a copy cost (DESIGN.md §Multi-tier
        KV)."""
        if not self.prefix_cache:
            return [], []
        return self.allocator.lookup_tiered(self._req_digests(req))

    def prefix_hint(self, req: ServeRequest):
        """(head_digest, cached_tokens, promote_blocks) for dispatch: the
        digest of the prompt's first full block (None for sub-block
        prompts), the tokens resident here across BOTH tiers, and how
        many of those blocks are host-resident (routing prices their
        promote copy — DESIGN.md §Multi-tier KV). The digest is
        content-derived, so it is identical across engines for the same
        prompt."""
        if not self.prefix_cache or len(req.prompt) <= self.block_size:
            return None, 0, 0
        digests = self._req_digests(req)
        dev, host = self.allocator.lookup_tiered(digests)
        return digests[0], (len(dev) + len(host)) * self.block_size, len(host)

    def prefix_digests(self) -> frozenset:
        """Head digests of every cached chain (either tier) — the compact
        advertisement within-stage dispatch tie-breaks on."""
        if not self.paged or not self.prefix_cache:
            return frozenset()
        return (self.allocator.head_digests()
                | self.allocator.host_head_digests())

    def tiered_digests(self) -> Dict[int, str]:
        """Head digest -> tier tag ('device' | 'host'). The control
        plane's warm filter prefers device-warm instances — a host hit
        still beats recompute but pays the promote copy (DESIGN.md
        §Multi-tier KV)."""
        if not self.paged or not self.prefix_cache:
            return {}
        out = {h: "device" for h in self.allocator.head_digests()}
        for h in self.allocator.host_head_digests():
            out.setdefault(h, "host")
        return out

    # ---- multi-tier KV (DESIGN.md §Multi-tier KV) ----------------------------
    def _demote_snapshot(self, block_id: int):
        """Payload fetch the allocator calls when reclaiming a cached
        block with the host tier on: an ASYNC device-side slice of the
        block ([L, 1, Hkv, BS, ...]; int8 pools carry their scale leaves in
        the same pytree). Dispatch order guarantees the copy reads the
        block BEFORE the allocation that triggered the reclaim overwrites
        it; the host transfer itself happens at ``_flush_demotes`` — off
        the decode hot loop, after the step's single d2h."""
        return jax.tree.map(lambda a: a[:, block_id:block_id + 1],
                            self.cache)

    def _flush_demotes(self) -> None:
        """Materialize this step's demoted payloads to host numpy. NOT
        routed through :func:`d2h` on purpose: the step's one-d2h
        contract is about the decode hot loop's sync token; these copies
        were dispatched earlier and drain here, overlapped with the
        iteration that evicted them."""
        if self.paged and self.allocator.host_tier_enabled:
            self.allocator.host_materialize(
                lambda p: jax.tree.map(np.asarray, p))

    def _promote_blocks(self, req: ServeRequest, shared: List[int],
                        promo: List[int]) -> List[int]:
        """Promote a host-tier chain continuation onto device: allocate
        owned blocks (covered by the request's admission reservation),
        scatter all payloads in ONE async device call — the h2d copy
        overlaps the current mixed iteration; the request only
        chunk-prefills its truly-uncached tail afterwards — and
        re-publish each digest with its chain links restored."""
        # pop payloads BEFORE allocating: the allocation may reclaim (and
        # demote) other device blocks, and the resulting host-capacity
        # pressure must never evict the very entries being promoted
        payloads = [self.allocator.host_pop(h) for h in promo]
        ids = self.allocator.allocate(len(promo))
        piece = jax.tree.map(lambda *ps: jnp.concatenate(
            [jnp.asarray(p) for p in ps], axis=1), *payloads)
        self.cache = scatter_kv_blocks(self.cache, piece, ids)
        digests = self._req_digests(req)
        d0 = len(shared)
        for j, (b, h) in enumerate(zip(ids, promo)):
            parent = digests[d0 + j - 1] if d0 + j > 0 else 0
            self.allocator.publish(b, h, head=(d0 + j == 0), parent=parent)
        self.promoted_blocks_total += len(ids)
        return ids

    @property
    def cache_demotions(self) -> int:
        return self.allocator.cache_demotions if self.paged else 0

    @property
    def cache_drops(self) -> int:
        return self.allocator.cache_drops if self.paged else 0

    @property
    def cache_promotions(self) -> int:
        return self.allocator.cache_promotions if self.paged else 0

    def _publish_prompt(self, req: ServeRequest, slot: int) -> None:
        """Prefill finished: publish the prompt's FULL blocks into the
        prefix index (first writer wins; the partial tail block — which
        generation keeps writing — is never published). Extends the
        request's digest memo instead of re-hashing the prompt: the
        capped lookup chain misses at most the final full block
        (prompts whose length is an exact block multiple)."""
        table = self.block_tables[slot]
        digests = list(self._req_digests(req))
        n_full = len(req.prompt) // self.block_size
        if len(digests) < n_full:           # len(prompt) % BS == 0
            parent = digests[-1] if digests else 0
            start = len(digests) * self.block_size
            digests.append(chain_hash(
                parent, req.prompt[start:start + self.block_size]))
        for j, h in enumerate(digests[:n_full]):
            self.allocator.publish(table[j], h, head=(j == 0),
                                   parent=digests[j - 1] if j else 0)

    # ---- intake -------------------------------------------------------------
    def submit(self, req: ServeRequest) -> None:
        req.state = State.WAITING
        # prefix-hit hint for queued_tokens/load while the request waits
        # (refreshed authoritatively at admission) — both tiers count: a
        # host-resident chain still spares the queue its prefill work
        if self.paged and self.prefix_cache:
            dev, host = self._tiered_chain(req)
            req.cached_tokens = (len(dev) + len(host)) * self.block_size
        else:
            req.cached_tokens = 0
        if self.slo_sched:
            self._seq += 1
            req.sched_key = queue_key(req.slo_class, req.arrival_step,
                                      self._worst_tokens(req), self._seq,
                                      time_scale=self.slo_time_scale)
            insert_sorted(self.waiting, req)
        else:
            self.waiting.append(req)

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def _worst_tokens(self, req: ServeRequest) -> int:
        """Upper bound on this request's final cache length: generation
        stops at max_new_tokens or when the cache hits max_seq."""
        return min(len(req.prompt) + req.max_new_tokens, self.max_seq)

    def can_accept(self, req: ServeRequest) -> bool:
        """Slot + worst-case budget check (used for admission AND inbound
        migration, so both paths — and the server's receiver picking —
        share one accounting definition)."""
        if self._free_slot() is None or len(req.prompt) + 1 > self.max_seq:
            return False
        if req.state is State.RUNNING:
            # inbound migration: the remaining generation must fit this
            # engine's max_seq — rejecting here (and not only in
            # import_request) keeps _pick_receiver from choosing a
            # receiver that would refuse the import after the KV gather
            remaining = max(req.max_new_tokens - len(req.generated), 0)
            if req.length + remaining > self.max_seq:
                return False
        if self.paged:
            # admission reserves only the uncached tail: resident prefix
            # blocks are shared, not re-allocated — but sharing a PARKED
            # (refcount-0) chain revives it into cached_live, so the gate
            # charges that revival too or `reserved + cached_live` could
            # overshoot num_blocks. Migrated-in (RUNNING) requests
            # re-import as private, so they reserve true length.
            need = blocks_for(self._worst_tokens(req), self.block_size)
            if req.state is not State.RUNNING:
                chain = self._cached_chain(req)
                need += self.allocator.revival_cost(chain) - len(chain)
            return self.allocator.can_reserve(need)
        return self.reserved_tokens() + self._worst_tokens(req) \
            <= self.token_budget

    def _admit(self) -> List[ServeRequest]:
        """Admit FCFS while capacity lasts. Prompts that can NEVER fit this
        engine are failed (rejected=True) instead of wedging the queue —
        matching sim.Instance's documented semantics."""
        admitted = []
        if self.slo_sched:
            self._age_waiting()
            self._resume_ready()
        while self.waiting:
            req = self.waiting[0]
            if len(req.prompt) + 1 > self.max_seq:
                self.waiting.popleft()
                req.rejected = True
                req.state = State.FINISHED
                req.first_token_step = self.steps
                req.finish_step = self.steps
                admitted.append(req)
                continue
            if not self.can_accept(req):
                if self.slo_sched and self._preempt_for(req):
                    continue
                break
            slot = self._free_slot()
            self.waiting.popleft()
            self._stamp_admit(req)
            self._prefill_into_slot(req, slot)
            admitted.append(req)
        if self.slo_sched:
            self._resume_ready()
        return admitted

    def _stamp_admit(self, req: ServeRequest) -> None:
        """First admission: stamp ``t_admit`` and, for a request a server
        submitted, count its queue wait. Re-admissions (recompute resumes,
        redispatch after a crash) keep the first stamp."""
        if req.t_admit is not None:
            return
        req.t_admit = time.perf_counter()
        if req.t_submit is not None:
            self.admitted_total += 1
            self.queue_wait_s_total += req.t_admit - req.t_submit

    def _reserve(self, req: ServeRequest, slot: int,
                 cached_blocks: int = 0) -> None:
        worst = self._worst_tokens(req)
        if self.paged:
            rb = blocks_for(worst, self.block_size) - cached_blocks
            self.allocator.reserve(rb)
            self._slot_rblocks[slot] = rb
        self.slot_reserved[slot] = worst

    # ---- device-mirror helpers (paged + device_resident) ---------------------
    def _ensure_nbt_cap(self, need: int) -> None:
        """Grow the device block-table width to a pow2 >= need (capped at
        the max_seq block count) — O(log max_seq) recompiles total."""
        if need <= self._nbt_cap:
            return
        new = min(_next_pow2(need), blocks_for(self.max_seq, self.block_size))
        assert new >= need
        self._dev_bt = jnp.pad(self._dev_bt,
                               ((0, 0), (0, new - self._nbt_cap)),
                               constant_values=self.garbage_block)
        self._nbt_cap = new

    def _dev_set_table(self, slot: int, ids: List[int]) -> None:
        row = np.full((self._nbt_cap,), self.garbage_block, np.int32)
        row[:len(ids)] = ids
        self._dev_bt = self._dev_bt.at[slot].set(jnp.asarray(row))

    def _dev_clear_slot(self, slot: int) -> None:
        self._dev_bt = self._dev_bt.at[slot].set(self.garbage_block)
        self._dev_len = self._dev_len.at[slot].set(0)

    def _prefill_into_slot(self, req: ServeRequest, slot: int) -> None:
        if self.paged and self.device_resident:
            self._prefill_into_slot_device(req, slot)
            return
        tokens = jnp.asarray(req.prompt, jnp.int32)[None, :]
        self._reserve(req, slot)
        if self.paged:
            # prompt-length cache piece [L, 1, T, ...] scattered into
            # freshly allocated blocks — no max_seq padding anywhere
            logits, piece = attn_call(self._prefill, self.params,
                                      {"tokens": tokens}, cache_len=None)
            ids = self.allocator.allocate(
                blocks_for(len(req.prompt), self.block_size))
            self.block_tables[slot] = ids
            self.cache = _write_prompt_blocks(self.cache, piece, ids,
                                              self.block_size)
            self.prefill_work_blocks += len(ids)
            self.prefill_tokens_done += len(req.prompt)
        else:
            logits, piece = attn_call(self._prefill, self.params,
                                      {"tokens": tokens},
                                      cache_len=self.max_seq)
            self.cache = _write_slot(self.cache, piece, slot)
        vec = logits if logits.ndim == 1 else logits[0]
        tok = int(d2h(jnp.argmax(vec)))
        req.generated.append(tok)
        req.t_first_token = time.perf_counter()
        req.ctx_done = len(req.prompt)
        req.first_token_step = self.steps
        req.state = State.RUNNING
        req.engine_id = self.id
        req.slot = slot
        req.tokens_by_engine[self.id] = req.tokens_by_engine.get(self.id, 0) + 1
        self.slots[slot] = req
        self.slot_len[slot] = req.length
        self.tokens_out += 1

    def _prefill_into_slot_device(self, req: ServeRequest, slot: int) -> None:
        """Bucketed prefill with DEFERRED first-token sync: the prompt is
        padded to a pow2 length (one compile per bucket), the sampled
        first token stays on device (in ``_dev_tok`` and
        ``_pending_first``) and reaches ``generated`` at the step's single
        ``d2h``. All bookkeeping here is count-based, so nothing needs
        the token's value."""
        self._reserve(req, slot)
        T = len(req.prompt)
        P = min(_next_pow2(T), _next_pow2(self.max_seq))
        toks = np.zeros((1, P), np.int32)
        toks[0, :T] = req.prompt
        logits, piece = attn_call(
            self._prefill_bucketed, self.params,
            {"tokens": jnp.asarray(toks)}, jnp.int32(T))
        piece = jax.tree.map(lambda a: a[:, :, :T], piece)
        ids = self.allocator.allocate(blocks_for(T, self.block_size))
        self.block_tables[slot] = ids
        self.cache = _write_prompt_blocks(self.cache, piece, ids,
                                          self.block_size)
        self.prefill_work_blocks += len(ids)
        self.prefill_tokens_done += T
        tok_dev = jnp.argmax(logits[0]).astype(jnp.int32)
        self._ensure_nbt_cap(len(ids))
        self._dev_set_table(slot, ids)
        self._dev_len = self._dev_len.at[slot].set(T + 1)
        self._dev_tok = self._dev_tok.at[slot].set(tok_dev)
        self._pending_first.append((req, tok_dev))
        req.ctx_done = T
        req.first_token_step = self.steps
        req.state = State.RUNNING
        req.engine_id = self.id
        req.slot = slot
        req.tokens_by_engine[self.id] = req.tokens_by_engine.get(self.id, 0) + 1
        self.slots[slot] = req
        self.slot_len[slot] = T + 1
        self.tokens_out += 1

    # ---- chunked prefill: the mixed-iteration prompt side --------------------
    # (DESIGN.md §Chunked prefill.) Each step packs up to
    # ``prefill_token_budget`` prompt-chunk tokens — resuming in-progress
    # prefills first (oldest admitted first), then admitting from the FCFS
    # queue while budget and capacity last. Chunk K/V goes straight into
    # freshly allocated pool blocks, so a partial prompt is ordinary pool
    # state: it migrates, it is accounted, and the decode batch runs
    # beside it every single iteration — no head-of-line blocking.
    def _run_chunked_prefill(self) -> Tuple[List[ServeRequest],
                                            List[ServeRequest]]:
        """Returns (rejected, completed): requests failed for never
        fitting, and requests whose LAST chunk landed this step (their
        first token is sampled; device loops defer it to the step sync).
        This is the two-call reference path; the fused device loop plans
        with :meth:`_plan_chunks` and executes the chunks inside the ONE
        mixed device call instead."""
        rejected, plan = self._plan_chunks()
        completed: List[ServeRequest] = []
        if plan:
            arrays = self._prepare_chunk_arrays(plan)
            logits, self.cache = attn_call(self._prefill_chunk,
                                           self.params, self.cache, *arrays)
            self._finish_chunks(
                plan, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                completed)
        return rejected, completed

    def _plan_chunks(self) -> Tuple[List[ServeRequest],
                                    List[Tuple[int, int]]]:
        """Admission + chunk planning of the mixed iteration — pure host
        bookkeeping, no device work. Returns (rejected, plan) where plan
        is [(slot, chunk_len)] under the prefill token budget."""
        rejected: List[ServeRequest] = []
        if self.slo_sched:
            self._resume_ready()
        budget = self.prefill_token_budget
        plan: List[Tuple[int, int]] = []            # (slot, chunk_len)
        for slot in list(self._prefill_order):      # oldest admitted first
            if budget <= 0 or len(plan) == self._max_chunks:
                break
            req = self.slots[slot]
            clen = min(req.prefill_target_len - req.ctx_done, budget)
            plan.append((slot, clen))
            budget -= clen
        while (self.waiting and budget > 0
               and len(plan) < self._max_chunks):
            req = self.waiting[0]
            if len(req.prompt) + 1 > self.max_seq:  # can NEVER fit: fail
                self.waiting.popleft()
                req.rejected = True
                req.state = State.FINISHED
                req.first_token_step = self.steps
                req.finish_step = self.steps
                rejected.append(req)
                continue
            if not self.can_accept(req):
                if self.slo_sched and self._preempt_for(req):
                    continue
                break
            slot = self._free_slot()
            self.waiting.popleft()
            self._stamp_admit(req)
            # longest cached chain across both tiers: device blocks are
            # shared (refcount++, zero copies), host-tier continuations
            # PROMOTE — fresh owned blocks under this request's
            # reservation, one async h2d scatter overlapping the mixed
            # iteration — and chunking starts at ctx_done = cached_tokens,
            # so only the truly-uncached tail's prefill work ever runs
            # (DESIGN.md §Prefix cache, §Multi-tier KV)
            shared, promo = self._tiered_chain(req)
            self._reserve(req, slot, cached_blocks=len(shared))
            self._slot_shared[slot] = len(shared)
            if shared:
                self.allocator.share(shared)
            promoted = (self._promote_blocks(req, shared, promo)
                        if promo else [])
            req.cached_tokens = (len(shared) + len(promoted)) \
                * self.block_size
            self.cached_prompt_tokens_total += req.cached_tokens
            req.state = State.RUNNING
            req.engine_id = self.id
            req.slot = slot
            req.ctx_done = req.cached_tokens
            self.block_tables[slot] = list(shared) + promoted
            self.slots[slot] = req
            self.slot_len[slot] = req.ctx_done
            self._prefill_order.append(slot)
            clen = min(req.prefill_target_len - req.ctx_done, budget)
            plan.append((slot, clen))
            budget -= clen
        if self.slo_sched:
            self._resume_ready()
        return rejected, plan

    def _prepare_chunk_arrays(self, plan: List[Tuple[int, int]]):
        """Device arrays for ALL of the step's planned chunks — the prompt
        half of the mixed iteration, consumed either by the separate
        ``prefill_chunk`` call or by the fused mixed call. Chunks are
        padded to a common pow2 bucket and a common pow2 table width, the
        latter capped at the widest a chunk can need (compiles stay
        O(slots · log budget · log max_seq)); each chunk's
        blocks are allocated here, always covered by its admission
        reservation, so allocation cannot fail. Table tails are the
        garbage block, so the padding rows of short chunks never touch
        live data. Returns ``(tokens [B, C], tables [B, nbt], ctx [B],
        clen [B])``."""
        B = len(plan)
        C = _next_pow2(max(clen for _, clen in plan))
        nbt = 1
        for slot, clen in plan:
            req = self.slots[slot]
            need = blocks_for(req.ctx_done + clen, self.block_size)
            table = self.block_tables[slot]
            if need > len(table):
                table.extend(self.allocator.allocate(need - len(table)))
            nbt = max(nbt, blocks_for(req.ctx_done + C, self.block_size))
            self.prefill_work_blocks += need    # grid-step mirror
            self.prefill_tokens_done += clen
        assert nbt <= self._max_chunk_nbt, (nbt, self._max_chunk_nbt)
        nbt = min(_next_pow2(nbt), self._max_chunk_nbt)
        toks = np.zeros((B, C), np.int32)
        bt = np.full((B, nbt), self.garbage_block, np.int32)
        ctxs = np.zeros((B,), np.int32)
        clens = np.zeros((B,), np.int32)
        for j, (slot, clen) in enumerate(plan):
            req = self.slots[slot]
            ctx = req.ctx_done
            # recompute-preempted requests rebuild KV for the resume
            # prefix (prompt + generated-so-far) instead of the prompt
            src = (req.resume_tokens if req.resume_tokens is not None
                   else req.prompt)
            toks[j, :clen] = src[ctx:ctx + clen]
            table = self.block_tables[slot]
            bt[j, :len(table)] = table
            ctxs[j] = ctx
            clens[j] = clen
        return (jnp.asarray(toks), jnp.asarray(bt), jnp.asarray(ctxs),
                jnp.asarray(clens))

    def _finish_chunks(self, plan: List[Tuple[int, int]], first_toks,
                       completed: List[ServeRequest]) -> None:
        """Post-chunk bookkeeping: advance ``ctx_done``, and for requests
        whose LAST chunk just landed, record the (still on-device) first
        token — ``first_toks`` is the int32 [B] argmax over each chunk's
        final-position logits. On the fused path the completing slot was
        dead during the device call (its device table row all-garbage, its
        length 0), so publishing its table/length here — after the call —
        means a request never decodes in the same step its prefill
        finishes; token VALUES are unaffected."""
        for j, (slot, clen) in enumerate(plan):
            req = self.slots[slot]
            T = req.prefill_target_len
            req.ctx_done += clen
            self.slot_len[slot] = req.ctx_done
            if req.ctx_done < T:
                continue
            if req.prefill_target is not None:
                # recompute resume complete: rows 0..T-1 rebuilt, decoding
                # continues from generated[-1] at position T next decode.
                # The chunk's final-position logits reproduce that token's
                # argmax — discarded, no new sample, no re-publish.
                self._finish_resume(req, slot, T)
                continue
            # final chunk: the first token exists; the finished prompt's
            # full blocks become shareable for every later arrival
            if self.prefix_cache:
                self._publish_prompt(req, slot)
            self._prefill_order.remove(slot)
            tok_dev = first_toks[j]
            req.first_token_step = self.steps
            req.tokens_by_engine[self.id] = \
                req.tokens_by_engine.get(self.id, 0) + 1
            self.tokens_out += 1
            self.slot_len[slot] = T + 1
            if self.device_resident:
                # token stays on device; it reaches the host (and
                # req.generated) at the step's single d2h
                table = self.block_tables[slot]
                self._ensure_nbt_cap(len(table))
                self._dev_set_table(slot, table)
                self._dev_len = self._dev_len.at[slot].set(T + 1)
                self._dev_tok = self._dev_tok.at[slot].set(tok_dev)
                self._pending_first.append((req, tok_dev))
            else:
                req.generated.append(int(d2h(tok_dev)))
                req.t_first_token = time.perf_counter()
            completed.append(req)

    # ---- SLO preemption (DESIGN.md §SLO scheduling & preemption) -------------
    def _victim_slots(self, pr: int) -> List[int]:
        """Preemptable slots for a priority-``pr`` preemptor: strictly
        lower class (so uniform-class traffic never preempts and cannot
        thrash), fully prefilled, with >= 1 synced generated token (a
        device-path request whose first token is still in-flight has no
        host-visible continuation point yet)."""
        return [i for i, r in enumerate(self.slots)
                if r is not None and not r.prefilling and r.generated
                and priority_of(r.slo_class) > pr]

    def _mem_shortfall(self, req: ServeRequest) -> int:
        """Blocks the allocator is short of admitting ``req`` (<= 0 means
        the blocker is a slot, not memory)."""
        if not self.paged:
            return 0
        need = blocks_for(self._worst_tokens(req), self.block_size)
        if req.state is not State.RUNNING:
            chain = self._cached_chain(req)
            need += self.allocator.revival_cost(chain) - len(chain)
        return need - self.allocator.headroom_blocks

    def _preempt_for(self, req: ServeRequest) -> bool:
        """Make room for a blocked higher-class request by preempting the
        lowest-class, largest resident victim: park it (slot shortage —
        blocks and reservation stay put) or drop-and-recompute its KV
        (memory shortage — parking frees nothing). Returns True if a
        victim was preempted; the caller re-checks admission."""
        if not self.paged:
            return False        # a monolithic slot IS its memory: no park
        if (req.generated and req.first_token_step is not None
                and tpot_hopeless(req.slo_class, req.first_token_step,
                                  self.steps, req.max_new_tokens,
                                  time_scale=self.slo_time_scale)):
            # TPOT-deadline admission: this resumed decode has already
            # blown its per-token deadline beyond recovery — preempting
            # healthy traffic for it buys no attainment. It waits for
            # organic capacity and is counted against attainment.
            if req.req_id not in self._tpot_hopeless_ids:
                self._tpot_hopeless_ids.add(req.req_id)
                self.tpot_skipped += 1
            return False
        pr = priority_of(req.slo_class)
        short = self._mem_shortfall(req)
        cands = self._victim_slots(pr)
        if not cands:
            # memory may be pinned only by parked lower-class requests:
            # recompute-preempt the largest of those instead
            return short > 0 and self._preempt_parked(pr)
        slot = max(cands, key=lambda i: (
            priority_of(self.slots[i].slo_class), len(self.block_tables[i])))
        mode = park_or_recompute(must_free_blocks=max(short, 0),
                                 kv_tokens=int(self.slot_len[slot]) - 1)
        if mode == "recompute":
            if not self.chunked_prefill:
                return False    # nowhere to rebuild the KV from
            self._preempt_recompute(slot)
        else:
            self._preempt_park(slot)
        return True

    def _preempt_park(self, slot: int) -> None:
        """Pause a resident decode keeping its KV: blocks pin via
        ``BlockAllocator.park`` and the reservation stays, so resume is a
        pure bookkeeping restore — bit-identical continuation."""
        req = self.slots[slot]
        table = self.block_tables[slot]
        self.allocator.park(table)
        self._seq += 1
        # size 0: a parked request outranks an equal-deadline waiting one
        # (its restore is free; re-admitting the other is not)
        req.sched_key = queue_key(req.slo_class, req.arrival_step, 0.0,
                                  self._seq, time_scale=self.slo_time_scale)
        self.parked.append(_Parked(req, table, self._slot_shared[slot],
                                   self._slot_rblocks[slot],
                                   int(self.slot_len[slot])))
        self._slot_shared[slot] = 0
        self._slot_rblocks[slot] = 0
        self.block_tables[slot] = []
        if self.device_resident:
            self._dev_clear_slot(slot)
        self.slots[slot] = None
        self.slot_len[slot] = 0
        self.slot_reserved[slot] = 0
        req.slot = None
        req.state = State.PREEMPTED
        req.preemptions += 1
        self.preemptions += 1

    def _preempt_recompute(self, slot: int) -> None:
        """Drop a resident decode's KV entirely (blocks + reservation) and
        re-enqueue it to rebuild via chunked prefill over its resume
        prefix — the memory-pressure exit."""
        req = self.slots[slot]
        written = int(self.slot_len[slot]) - 1
        self._release(slot)
        self._requeue_recompute(req, written)

    def _preempt_parked(self, pr: int) -> bool:
        """Recompute-preempt the largest parked request of a class below
        ``pr``: the only way to free memory held by parked victims."""
        if not self.chunked_prefill:
            return False
        cands = [p for p in self.parked if priority_of(p.req.slo_class) > pr]
        if not cands:
            return False
        rec = max(cands, key=lambda p: (priority_of(p.req.slo_class),
                                        len(p.table)))
        self.parked.remove(rec)
        self.allocator.unpark(rec.table)
        if rec.shared:
            self.allocator.release(rec.table[:rec.shared], owned=False)
            self.allocator.release(rec.table[rec.shared:], owned=True)
        else:
            self.allocator.release(rec.table, owned=True)
        self.allocator.unreserve(rec.rblocks)
        self._requeue_recompute(rec.req, rec.slot_len - 1)
        return True

    def _requeue_recompute(self, req: ServeRequest, written: int) -> None:
        """Re-enqueue a preempted decode as a resume job: prefill must
        rebuild ``written`` rows (= prompt + generated[:-1]); the last
        sampled token then decodes at position ``written`` exactly as it
        would have unpreempted."""
        req.prefill_target = written
        req.resume_tokens = np.concatenate(
            [np.asarray(req.prompt, np.int32),
             np.asarray(req.generated[:-1], np.int32)])
        assert len(req.resume_tokens) == written
        req.ctx_done = 0
        req.cached_tokens = 0
        req.slot = None
        req.state = State.WAITING
        req.preemptions += 1
        req.preempted_step = self.steps      # aging clock starts now
        self.preemptions += 1
        self.preempt_recomputes += 1
        self._seq += 1
        req.sched_key = queue_key(req.slo_class, req.arrival_step,
                                  self._worst_tokens(req), self._seq,
                                  time_scale=self.slo_time_scale)
        insert_sorted(self.waiting, req)

    def _age_waiting(self) -> None:
        """Starvation/aging guard (DESIGN.md §SLO scheduling): a
        recompute-preempted request still waiting climbs one priority
        class per TTFT budget elapsed since its preemption
        (sched.slo.aging_promotion), so saturated higher-class traffic
        cannot starve it forever. Keys keep their original deadline/size/
        seq components — within a promoted class the victim competes on
        its true deadline."""
        changed = False
        for req in self.waiting:
            if req.preempted_step is None:
                continue
            promote = aging_promotion(req.slo_class, req.preempted_step,
                                      self.steps,
                                      time_scale=self.slo_time_scale)
            if promote <= 0:
                continue
            key = queue_key(req.slo_class, req.arrival_step,
                            self._worst_tokens(req), req.sched_key[3],
                            time_scale=self.slo_time_scale, promote=promote)
            if key != req.sched_key:
                req.sched_key = key
                changed = True
        if changed:
            ordered = sorted(self.waiting, key=lambda r: r.sched_key)
            self.waiting.clear()
            self.waiting.extend(ordered)

    def _resume_ready(self) -> None:
        """Restore parked requests into free slots — unless a waiting
        request outranks the best parked one (preemption must not invert
        the queue order it enforced)."""
        while self.parked:
            slot = self._free_slot()
            if slot is None:
                return
            rec = min(self.parked, key=lambda p: p.req.sched_key)
            if self.waiting and self.waiting[0].sched_key < rec.req.sched_key:
                return
            self.parked.remove(rec)
            self._unpark(rec, slot)

    def _unpark(self, rec: _Parked, slot: int) -> None:
        req = rec.req
        self.allocator.unpark(rec.table)
        self.block_tables[slot] = rec.table
        self._slot_shared[slot] = rec.shared
        self._slot_rblocks[slot] = rec.rblocks
        self.slots[slot] = req
        self.slot_len[slot] = rec.slot_len
        self.slot_reserved[slot] = self._worst_tokens(req)
        req.slot = slot
        req.state = State.RUNNING
        self.resumes += 1
        if self.device_resident:
            self._ensure_nbt_cap(len(rec.table))
            self._dev_set_table(slot, rec.table)
            self._dev_len = self._dev_len.at[slot].set(rec.slot_len)
            self._dev_tok = self._dev_tok.at[slot].set(int(req.generated[-1]))

    def _finish_resume(self, req: ServeRequest, slot: int, T: int) -> None:
        """A recompute resume's last chunk landed: rows 0..T-1 are back;
        re-arm decode so ``generated[-1]`` writes row T next step. No
        token is sampled and nothing is re-published — the continuation
        is the original request's, bit for bit."""
        self._prefill_order.remove(slot)
        req.prefill_target = None
        req.resume_tokens = None
        req.ctx_done = len(req.prompt)
        self.slot_len[slot] = T + 1
        self.resumes += 1
        if self.device_resident:
            table = self.block_tables[slot]
            self._ensure_nbt_cap(len(table))
            self._dev_set_table(slot, table)
            self._dev_len = self._dev_len.at[slot].set(T + 1)
            self._dev_tok = self._dev_tok.at[slot].set(int(req.generated[-1]))

    # ---- one continuous-batching iteration ----------------------------------
    def step(self, burst: int = 1) -> List[ServeRequest]:
        """Advance the engine and return requests that finished.

        ``burst > 1`` (device-resident paged engines only) fuses up to
        that many consecutive decode iterations into one ``lax.scan``
        micro-batch with a single device→host transfer; the fusion is
        clamped so no request can hit its token-count or max_seq finish
        boundary before the last fused iteration, hence admission is
        never starved (capacity only frees at a finish)."""
        if self.paged and self.device_resident:
            return self._step_device(burst)
        return self._step_host()

    def _step_host(self) -> List[ServeRequest]:
        """The original host-driven loop (monolithic engines, and paged
        with ``device_resident=False`` — the bit-parity reference)."""
        self.steps += 1
        finished: List[ServeRequest] = []
        if self.chunked_prefill:
            rejected, prefilled = self._run_chunked_prefill()
            finished.extend(rejected)
            for r in prefilled:
                if r.done:      # max_new_tokens == 1 / eos first token
                    r.state = State.FINISHED
                    r.finish_step = self.steps
                    finished.append(r)
                    self._release(r.slot)
        else:
            for r in self._admit():
                if r.rejected:                  # prompt can never fit
                    finished.append(r)
                elif r.done:    # max_new_tokens == 1: prefill already
                    r.state = State.FINISHED    # produced the only token
                    r.finish_step = self.steps
                    finished.append(r)
                    self._release(r.slot)
        # requests still mid-prefill hold their slot but do NOT decode
        live = [(i, r) for i, r in enumerate(self.slots)
                if r is not None and not r.prefilling]
        if live:
            last_tok = jnp.asarray(
                [r.generated[-1] if r.generated else r.prompt[-1]
                 for _, r in live], jnp.int32)
            pos = jnp.asarray([self.slot_len[i] - 1 for i, _ in live],
                              jnp.int32)
            if self.paged:
                logits = self._decode_paged_live(live, last_tok, pos)
            else:
                logits = self._decode_mono_live(live, last_tok, pos)
            toks = d2h(jnp.argmax(logits, axis=-1))   # one transfer, fused
            for j, (i, r) in enumerate(live):
                tok = int(toks[j])
                r.generated.append(tok)
                r.tokens_by_engine[self.id] = \
                    r.tokens_by_engine.get(self.id, 0) + 1
                self.tokens_out += 1
                self.slot_len[i] += 1
                if r.done or self.slot_len[i] >= self.max_seq:
                    r.state = State.FINISHED
                    r.finish_step = self.steps
                    finished.append(r)
                    self._release(i)
        self._flush_demotes()
        self.peak_kv_bytes = max(self.peak_kv_bytes, self.kv_bytes_pinned())
        assert self.free_tokens() >= 0, "admission let the budget go negative"
        return finished

    # ---- device-resident step (paged default) --------------------------------
    def _burst_fn(self, num_work: int, horizon: int):
        """Jitted ``horizon``-iteration decode micro-batch, cached per
        (num_work, horizon) — both pow2-bucketed, and ``num_work`` is 0
        but on the "flat" backend, so the cache stays O(log² ·). Shape
        changes (table width growth) retrace via jit."""
        key = (num_work, horizon)
        fn = self._burst_fns.get(key)
        if fn is not None:
            return fn
        decode = functools.partial(self.model.decode_step_paged,
                                   attn_backend=self.attn_backend,
                                   attn_interpret=self.attn_interpret,
                                   attn_num_work=num_work)

        def burst(params, cache, bt, tok, length):
            def one(carry, _):
                cache, tok, length = carry
                live = length > 0
                pos = length - 1            # dead slots: -1 -> 0 attn length
                logits, cache = decode(params, cache, tok, bt, pos)
                new_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                tok = jnp.where(live, new_tok, tok)
                length = jnp.where(live, length + 1, length)
                return (cache, tok, length), new_tok

            if horizon == 1:    # plain call — no scan carry round-trip
                (cache, tok, length), toks = one((cache, tok, length), None)
                return cache, tok, length, toks[None]
            (cache, tok, length), toks = jax.lax.scan(
                one, (cache, tok, length), None, length=horizon)
            return cache, tok, length, toks    # toks [horizon, max_slots]

        if self.tp > 1:
            burst = self._smap(burst,
                               (self._pspec, self._pool_spec, P(), P(), P()),
                               (self._pool_spec, P(), P(), P()))
        fn = jax.jit(burst)
        self._burst_fns[key] = fn
        return fn

    def _mixed_fn(self):
        """Jitted FUSED mixed iteration: the whole decode batch and the
        step's prompt chunks advance through the stack in this single
        attention-bearing call — one tagged work-list kernel launch per
        layer (DESIGN.md §Fused mixed-iteration attention). One function
        per engine: the kernel counts its work items on the device, so
        only shape changes (table width, chunk bucket, chunk count)
        retrace via jit."""
        mixed = functools.partial(self.model.mixed_step,
                                  attn_backend=self.attn_backend,
                                  attn_interpret=self.attn_interpret)

        def step(params, cache, bt, tok, length, ck_tokens, bt_ck, ctx, clen):
            live = length > 0
            pos = length - 1            # dead slots: -1 -> 0 attn length
            dec_logits, ck_logits, cache = mixed(
                params, cache, tok, ck_tokens, bt, bt_ck, pos, ctx, clen)
            new_tok = jnp.argmax(dec_logits, axis=-1).astype(jnp.int32)
            tok = jnp.where(live, new_tok, tok)
            length = jnp.where(live, length + 1, length)
            ck_tok = jnp.argmax(ck_logits, axis=-1).astype(jnp.int32)
            return cache, tok, length, new_tok, ck_tok

        if self.tp > 1:
            step = self._smap(step, (self._pspec, self._pool_spec,
                                     P(), P(), P(), P(), P(), P(), P()),
                              (self._pool_spec, P(), P(), P(), P()))
        return jax.jit(step)

    def _grow_tables(self, live, h: int) -> int:
        """Pre-grow the live rows' block tables to cover every write of an
        ``h``-iteration step (positions slot_len-1 .. slot_len+h-2) —
        covered by the admission reservations, so allocation cannot fail
        — with one device write per grown row. Returns the rows grown."""
        rows = 0
        for i, _ in live:
            need = blocks_for(int(self.slot_len[i]) + h - 1, self.block_size)
            table = self.block_tables[i]
            if need > len(table):
                table.extend(self.allocator.allocate(need - len(table)))
                self._ensure_nbt_cap(need)
                self._dev_set_table(i, table)
                rows += 1
        return rows

    def _step_device(self, burst: int) -> List[ServeRequest]:
        self.steps += 1
        base = self.steps                  # engine step of the 1st iteration
        finished: List[ServeRequest] = []
        self._pending_first = []
        prefill_done: List[ServeRequest] = []
        chunk_plan: List[Tuple[int, int]] = []
        admitted0, wait0 = self.admitted_total, self.queue_wait_s_total
        # admission, preemption and chunk planning (the separate-kernel and
        # whole-prompt paths run their prefill calls in here too)
        with _span("engine.plan", eng=self.id) as sp:
            if self.chunked_prefill:
                if self.fused_mixed:
                    # plan + admit only — the chunks execute INSIDE the
                    # fused mixed call below, not as a separate device call
                    rejected, chunk_plan = self._plan_chunks()
                    finished.extend(rejected)
                else:
                    rejected, prefilled = self._run_chunked_prefill()
                    finished.extend(rejected)
                    for r in prefilled:
                        if r.max_new_tokens <= 1:   # finishes at prefill;
                            prefill_done.append(r)  # its token lands after
                            self._release(r.slot)   # the sync
            else:
                for r in self._admit():
                    if r.rejected:              # prompt can never fit
                        finished.append(r)
                    elif r.max_new_tokens <= 1:     # finishes at prefill;
                        prefill_done.append(r)      # its token lands after
                        self._release(r.slot)       # the sync
            # requests still mid-prefill hold their slot but do NOT
            # decode: their device table row stays all-garbage and their
            # length 0, so the fixed-shape batch treats them as dead slots
            live = [(i, r) for i, r in enumerate(self.slots)
                    if r is not None and not r.prefilling]
            if _span.is_enabled():
                sp.set_metadata(
                    admitted=self.admitted_total - admitted0,
                    wait_us=round((self.queue_wait_s_total - wait0) * 1e6),
                    chunk_tokens=sum(c for _, c in chunk_plan))
        h = 0
        toks = None
        if self.fused_mixed and chunk_plan:
            # ---- ONE fused device call: decode batch + prompt chunks ----
            # (DESIGN.md §Fused mixed-iteration attention.) h = 1 always —
            # a step with chunk work is an admission opportunity, so it
            # never bursts (same rule as the separate path's cap)
            h = 1
            with _span("engine.tables", eng=self.id) as sp:
                rows = self._grow_tables(live, h)
                if _span.is_enabled():
                    sp.set_metadata(rows=rows)
            with _span("engine.stage", eng=self.id):
                ck_toks, bt_ck, ctxs, clens = \
                    self._prepare_chunk_arrays(chunk_plan)
            # the launch, and the device writes that hand the prompts it
            # completes to the decode batch
            with _span("engine.launch", eng=self.id, kind="mixed") as sp:
                dec_blocks = [blocks_for(int(self.slot_len[i]),
                                         self.block_size) for i, _ in live]
                ck_blocks = [blocks_for(self.slots[s].ctx_done + c,
                                        self.block_size)
                             for s, c in chunk_plan]
                real = sum(dec_blocks) + sum(ck_blocks)
                # the kernel runs exactly the real items, counted on the
                # device, over a work list as long as its segments x
                # their common table width
                self.work_items += real
                self.real_work_items += real
                # the whole decode batch and every chunk row run through
                # the stack; each chunk samples at its last row
                nck, ck_len = ck_toks.shape
                coll = self._collective_bytes(
                    self.max_slots + nck * ck_len, self.max_slots + nck)
                self.collective_bytes += coll or 0
                if _span.is_enabled():
                    stats = dict(horizon=h, items=real, real_items=real,
                                 capacity=(self.max_slots + len(chunk_plan))
                                 * max(self._nbt_cap, bt_ck.shape[1]))
                    if coll is not None:
                        stats["collective_bytes"] = coll
                    sp.set_metadata(**stats)
                (self.cache, self._dev_tok, self._dev_len, new_tok,
                 ck_tok) = attn_call(self._mixed, self.params, self.cache,
                                     self._dev_bt, self._dev_tok,
                                     self._dev_len, ck_toks, bt_ck, ctxs,
                                     clens)
                if live:
                    toks = new_tok[None]    # one horizon row for the sync
                    self.mixed_steps += 1
                else:
                    h = 0
                chunk_completed: List[ServeRequest] = []
                self._finish_chunks(chunk_plan, ck_tok, chunk_completed)
                for r in chunk_completed:
                    if r.max_new_tokens <= 1:   # finishes at prefill; its
                        prefill_done.append(r)  # token lands after the sync
                        self._release(r.slot)
        elif live:
            # the burst's horizon, and the block tables grown to cover it
            with _span("engine.tables", eng=self.id) as sp:
                pend_reqs = {id(r) for r, _ in self._pending_first}

                # fusion horizon: nobody may cross a count/capacity finish
                # boundary before the last fused iteration (eos finishes
                # are data-dependent and handled by truncation after the
                # sync)
                def _until_finish(i, r):
                    gen = len(r.generated) + (1 if id(r) in pend_reqs
                                              else 0)
                    return min(r.max_new_tokens - gen,
                               self.max_seq - int(self.slot_len[i]))
                # only NO-admission steps fuse: with a non-empty queue (or
                # a prompt mid-chunked-prefill) every step is an admission
                # / chunk opportunity, so stay at h=1 — this is also what
                # caps a decode request's inter-token gap at ONE mixed
                # iteration
                cap = 1 if (self.waiting or self._prefill_order
                            or self.parked) else burst
                h = max(1, min([cap] + [_until_finish(i, r)
                                        for i, r in live]))
                h = _pow2_floor(h)
                rows = self._grow_tables(live, h)
                if _span.is_enabled():
                    sp.set_metadata(rows=rows)
            with _span("engine.launch", eng=self.id, kind="burst") as sp:
                lens = [int(self.slot_len[i]) for i, _ in live]
                # iteration s attends over slot_len + s rows
                real = sum(blocks_for(n + s, self.block_size)
                           for s in range(h) for n in lens)
                # the fused kernel runs the real items, counted on the
                # device, over a max_slots x table-width work list; the
                # others a static bucket covering the burst's longest
                # contexts
                fused = self.attn_backend == "fused"
                items = real if fused else h * _next_pow2(sum(
                    blocks_for(n + h - 1, self.block_size) for n in lens))
                self.work_items += items
                self.real_work_items += real
                # every iteration runs and samples the whole batch
                coll = self._collective_bytes(h * self.max_slots,
                                              h * self.max_slots)
                self.collective_bytes += coll or 0
                if _span.is_enabled():
                    stats = dict(horizon=h, items=items, real_items=real,
                                 capacity=(self.max_slots * self._nbt_cap * h
                                           if fused else items))
                    if coll is not None:
                        stats["collective_bytes"] = coll
                    sp.set_metadata(**stats)
                # num_work only shapes the flat grid's static work list;
                # for the other backends key the jit cache on a single
                # value so growth of the live block count never forces a
                # recompile
                num_work = items // h if self.attn_backend == "flat" else 0
                fn = self._burst_fn(num_work, h)
                self.cache, self._dev_tok, self._dev_len, toks = attn_call(
                    fn, self.params, self.cache, self._dev_bt, self._dev_tok,
                    self._dev_len)
        # ---- the step's single device->host transfer ----
        with _span("engine.d2h", eng=self.id):
            pending = list(self._pending_first)
            parts = [jnp.stack([t for _, t in pending])] if pending else []
            if toks is not None:
                parts.append(toks.reshape(-1))
            host = (d2h(jnp.concatenate(parts)) if parts
                    else np.zeros(0, np.int32))
        with _span("engine.commit", eng=self.id) as sp:
            now = time.perf_counter()
            first = host[:len(pending)]
            rest = host[len(pending):].reshape(h, self.max_slots) if h \
                else None
            # prefill first tokens (deferred appends)
            for (r, _), tok in zip(pending, first):
                r.generated.append(int(tok))
                if r.t_first_token is None:
                    r.t_first_token = now
            for r in prefill_done:
                r.state = State.FINISHED
                r.finish_step = base
                finished.append(r)
            # an admitted request whose FIRST token was eos is done before
            # the burst tokens; its fused decodes wrote only its own
            # pre-grown blocks, so truncating here is safe
            for i, r in live:
                if r.state is State.RUNNING and r.done:
                    r.state = State.FINISHED
                    r.finish_step = base
                    finished.append(r)
                    self._release(i)
            for s in range(h):
                for i, r in live:
                    if r.state is State.FINISHED:
                        continue
                    r.generated.append(int(rest[s, i]))
                    r.tokens_by_engine[self.id] = \
                        r.tokens_by_engine.get(self.id, 0) + 1
                    self.tokens_out += 1
                    self.slot_len[i] += 1
                    if r.done or self.slot_len[i] >= self.max_seq:
                        r.state = State.FINISHED
                        r.finish_step = base + s
                        finished.append(r)
                        self._release(i)
            self.steps = base + max(h - 1, 0)
            if _span.is_enabled():
                sp.set_metadata(finished=len(finished))
        # the demote flush, and the step's pool accounting
        with _span("engine.flush", eng=self.id):
            self._flush_demotes()
            self.peak_kv_bytes = max(self.peak_kv_bytes,
                                     self.kv_bytes_pinned())
            assert self.free_tokens() >= 0, \
                "admission let the budget go negative"
        return finished

    def _decode_mono_live(self, live, last_tok, pos):
        idx = np.asarray([i for i, _ in live])
        sub_cache = jax.tree.map(lambda a: a[:, idx], self.cache)
        logits, new_sub = attn_call(self._decode, self.params, sub_cache,
                                    last_tok, pos)
        # one batched scatter over all live slots (slots never alias, so
        # there are no duplicate indices) instead of a per-slot update
        self.cache = jax.tree.map(
            lambda a, p: a.at[:, idx].set(p.astype(a.dtype)),
            self.cache, new_sub)
        return logits

    def _decode_paged_live(self, live, last_tok, pos):
        # grow block tables so every request's write position is backed
        # (covered by its admission reservation — cannot fail)
        for i, _ in live:
            need = blocks_for(int(self.slot_len[i]), self.block_size)
            table = self.block_tables[i]
            if need > len(table):
                table.extend(self.allocator.allocate(need - len(table)))
        # bucketed table width: length-adaptive (max live blocks rounded to
        # a power of two) so short batches don't pay max_seq-wide gathers
        # but jit recompiles stay O(log) in sequence length
        nbt = max(len(self.block_tables[i]) for i, _ in live)
        nbt = min(_next_pow2(nbt), blocks_for(self.max_seq, self.block_size))
        bt = np.zeros((len(live), nbt), np.int32)
        for j, (i, _) in enumerate(live):
            ids = self.block_tables[i]
            bt[j, :len(ids)] = ids
        logits, self.cache = attn_call(
            self._decode_paged, self.params, self.cache, last_tok,
            jnp.asarray(bt), pos)
        return logits

    def _release(self, slot: int) -> None:
        if slot in self._prefill_order:     # evicted mid-prefill
            self._prefill_order.remove(slot)
        if self.paged:
            # shared prefix blocks (the table's head, taken via share at
            # admission) drop a borrowed reference; the private remainder
            # releases as owner. Published blocks at refcount 0 park in
            # the reclaimable LRU instead of freeing — still warm for the
            # next identical prefix.
            s = self._slot_shared[slot]
            self._slot_shared[slot] = 0
            table = self.block_tables[slot]
            if s:
                self.allocator.release(table[:s], owned=False)
                self.allocator.release(table[s:], owned=True)
            else:
                self.allocator.release(table, owned=True)
            self.block_tables[slot] = []
            self.allocator.unreserve(self._slot_rblocks[slot])
            self._slot_rblocks[slot] = 0
            if self.device_resident:
                self._dev_clear_slot(slot)
        self.slot_reserved[slot] = 0
        self.slots[slot] = None
        self.slot_len[slot] = 0

    # ---- correctness probe -------------------------------------------------
    def prompt_logits(self, prompt) -> np.ndarray:
        """First-step logits [V] (f32) of ``prompt`` through this engine's
        own chunked-prefill path — its backend, mesh and chunk size — over
        a scratch block table. The returned pool is dropped, so no request
        or allocator state changes: a probe for comparing backends and
        meshes on identical input, never part of serving."""
        assert self.chunked_prefill, "prompt_logits needs chunked prefill"
        C = self.prefill_token_budget
        T = len(prompt)
        padded = -(-T // C) * C
        need = blocks_for(padded, self.block_size)
        assert need <= self.num_blocks, "prompt exceeds the pool"
        # pow2 table width (one compile per width class); entries past
        # ``need`` are never reached and point at the garbage block
        bt = jnp.minimum(jnp.arange(_next_pow2(need), dtype=jnp.int32),
                         self.garbage_block)[None]
        toks = np.zeros((1, padded), np.int32)
        toks[0, :T] = prompt
        pool = self.cache
        for ctx in range(0, T, C):
            clen = min(C, T - ctx)
            logits, pool = self._prefill_chunk(
                self.params, pool, jnp.asarray(toks[:, ctx:ctx + C]), bt,
                jnp.int32(ctx), jnp.int32(clen))
        return np.asarray(logits[0], np.float32)

    # ---- migration ----------------------------------------------------------
    def export_slot(self, slot: int):
        """(request, kv piece, kv bytes) for live migration.

        The piece is the wire format of DESIGN.md §Migration: contiguous
        ``[L, 1, written, ...]`` — a gather over the request's blocks on
        the paged path, a trimmed slab slice on the monolithic one — so
        bytes moved scale with the request's actual length, and paged and
        monolithic engines interoperate. ``written = slot_len - 1``: the
        latest sampled token's KV is produced by the *next* decode step
        (on whichever engine runs it), so both layouts export exactly the
        rows that exist — the paged block count always covers them. A
        request still mid-chunked-prefill has no sampled token: every one
        of its ``ctx_done`` written rows ships (``slot_len == ctx_done``),
        and the receiver resumes chunking from there (DESIGN.md §Chunked
        prefill, partial-prefill migration).
        """
        req = self.slots[slot]
        assert req is not None
        length = int(self.slot_len[slot]) - (0 if req.prefilling else 1)
        if self.paged:
            gathered = gather_kv_blocks(self.cache, self.block_tables[slot])
            # pool blocks -> [L, 1, nb*BS, Hkv, Dh] -> trim to length
            piece = jax.tree.map(
                lambda a: a[:, :, :length],
                blocks_to_piece(gathered, self.model.cfg.head_dim))
            if isinstance(piece, QuantKVCache):
                # wire format stays full-width: mixed bf16/int8 clusters
                # interoperate, receivers re-quantize on import
                piece = dequantize_piece(piece, self.model.cfg.dtype)
        else:
            piece = jax.tree.map(lambda a: a[:, slot:slot + 1], self.cache)
            if self.model.cfg.family != "ssm" \
                    and not self.model.cfg.sliding_window:
                piece = jax.tree.map(lambda a: a[:, :, :length], piece)
        return req, piece, kv_bytes(piece)

    def evict_slot(self, slot: int) -> None:
        self._release(slot)

    def import_request(self, req: ServeRequest, piece) -> bool:
        """Adopt a migrated request plus its KV piece — still-decoding, or
        still mid-chunked-prefill (``req.ctx_done < len(prompt)``): the
        piece then holds the ``ctx_done`` written rows and this engine
        resumes chunking where the source stopped. Rejects (via
        ``can_accept``) when no slot is free, the remaining generation
        cannot fit ``max_seq``, or the worst-case footprint exceeds the
        free budget — and partial prompts when this engine cannot chunk."""
        if req.prefilling and not self.chunked_prefill:
            return False        # nowhere to resume the prompt from
        if not self.can_accept(req):
            return False
        slot = self._free_slot()
        piece = self._localize_piece(piece)
        # a migrated shared prefix re-imports as PRIVATE (DESIGN.md
        # §Prefix cache): the wire piece is a plain contiguous gather, the
        # receiver allocates fresh blocks and reserves true length —
        # sharing is re-established only by the receiver's own index
        req.cached_tokens = 0
        self._reserve(req, slot)
        if self.paged and req.prefilling:
            written = req.ctx_done
            nb = blocks_for(written, self.block_size)
            ids = self.allocator.allocate(nb)
            self.block_tables[slot] = ids
            if nb:
                self.cache = _write_prompt_blocks(self.cache, piece, ids,
                                                  self.block_size)
            self._prefill_order.append(slot)   # resume chunking next step
            req.engine_id = self.id
            req.slot = slot
            req.state = State.RUNNING
            req.tokens_by_engine.setdefault(self.id, 0)
            self.slots[slot] = req
            self.slot_len[slot] = written
            # device mirrors stay cleared (all-garbage table, length 0):
            # the decode batch treats a mid-prefill slot as dead
            self._flush_demotes()   # import allocation may have demoted
            return True
        if self.paged:
            length = req.length
            nb = blocks_for(length, self.block_size)
            ids = self.allocator.allocate(nb)
            self.block_tables[slot] = ids
            self.cache = _write_prompt_blocks(self.cache, piece, ids,
                                              self.block_size)
            if self.device_resident:
                # adopted requests always carry >= 1 generated token, so
                # the device mirror seeds from host values (no sync)
                self._ensure_nbt_cap(nb)
                self._dev_set_table(slot, ids)
                self._dev_len = self._dev_len.at[slot].set(length)
                self._dev_tok = self._dev_tok.at[slot].set(
                    int(req.generated[-1]))
        else:
            self.cache = _write_slot(self.cache, piece, slot)
        req.engine_id = self.id
        req.slot = slot
        req.state = State.RUNNING
        # load-balance accounting (Fig. 16): the adopting engine must
        # appear in the per-engine token ledger even before its first token
        req.tokens_by_engine.setdefault(self.id, 0)
        self.slots[slot] = req
        self.slot_len[slot] = req.length
        self._flush_demotes()       # import allocation may have demoted
        return True


def _write_slot(cache, piece, slot: int):
    """Write a [L, 1, ...] piece into batch index ``slot`` of the cache.
    Leaves with a batch axis at position 1 are updated; piece S dim may be
    shorter than the cache's (trimmed migration pieces, prompt-length
    prefill pieces) — the remainder is zero-filled."""
    def put(a, p):
        p = p.astype(a.dtype)
        if p.shape[2:] != a.shape[2:]:
            pad = [(0, 0)] * p.ndim
            pad[2] = (0, a.shape[2] - p.shape[2])
            p = jnp.pad(p, pad)
        return jax.lax.dynamic_update_slice_in_dim(a, p, slot, axis=1)
    return jax.tree.map(put, cache, piece)


def _write_prompt_blocks(pool, piece, block_ids, block_size: int):
    """Scatter a contiguous KV piece (leaves [L, 1, T, Hkv, Dh]) into
    physical blocks ``block_ids`` of a paged pool (DESIGN.md §Block pool
    layout). Full-precision pieces headed for an int8 pool are quantized
    first."""
    if isinstance(pool, QuantKVCache) and not isinstance(piece, QuantKVCache):
        piece = quantize_piece(piece)
    blocks = piece_to_blocks(piece, len(block_ids), block_size,
                             jax.tree.leaves(pool)[0].shape[-1])
    return scatter_kv_blocks(pool, blocks, block_ids)
