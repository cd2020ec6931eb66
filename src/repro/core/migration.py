"""Live KV-cache migration (paper §5, adapting Llumnix's mechanism).

Multi-round live migration: while the source keeps decoding, round k
copies the KV written since round k−1 started; rounds shrink geometrically
until the residual is below ``stop_threshold`` tokens, then a brief
stop-and-copy finishes the hand-off. A per-instance concurrency cap
(3 transfers) and skip-if-no-idle-slot flow control are enforced by the
``MigrationManager``.

Two consumers:
  * the discrete-event simulator uses ``plan_live_migration`` timings;
  * the real in-process server moves actual KV pytrees with
    ``slice_kv_batch`` / ``merge_kv_batch`` (device-to-device copies —
    this container's stand-in for cudaMemcpyPeerAsync / RDMA).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

MAX_CONCURRENT = 3            # §5: strict concurrency limit
STOP_THRESHOLD = 256          # tokens left -> stop-and-copy
MAX_ROUNDS = 8


@dataclasses.dataclass(frozen=True)
class MigrationTiming:
    total_s: float            # wall time from start to ownership flip
    stall_s: float            # source decode stall (final round only)
    rounds: int
    bytes_moved: float


def plan_live_migration(tokens: float, decode_tok_per_s: float,
                        bytes_per_token: float, bandwidth: float,
                        stop_threshold: int = STOP_THRESHOLD) -> MigrationTiming:
    """Timing of a multi-round live migration of ``tokens`` KV tokens."""
    bw_tok = bandwidth / max(bytes_per_token, 1e-9)    # tokens/s on the wire
    remaining = float(tokens)
    total = 0.0
    moved = 0.0
    rounds = 0
    while remaining > stop_threshold and rounds < MAX_ROUNDS:
        t = remaining / bw_tok
        total += t
        moved += remaining
        # decode continued during the round: new residual to copy
        remaining = decode_tok_per_s * t
        rounds += 1
    stall = remaining / bw_tok                         # stop-and-copy
    total += stall
    moved += remaining
    return MigrationTiming(total_s=total, stall_s=stall, rounds=rounds + 1,
                           bytes_moved=moved * bytes_per_token)


class MigrationManager:
    """Concurrency + flow control for one instance's outbound transfers."""

    def __init__(self, max_concurrent: int = MAX_CONCURRENT):
        self.max_concurrent = max_concurrent
        self.active: Dict[int, float] = {}     # req_id -> finish time (sim)

    def can_start(self, target_has_idle_slot: bool) -> bool:
        # §5: skip migration entirely if the target has no idle cache slot;
        # requests above the concurrency cap stay on the source.
        return target_has_idle_slot and len(self.active) < self.max_concurrent

    def start(self, req_id: int, finish_time: float) -> None:
        assert len(self.active) < self.max_concurrent
        self.active[req_id] = finish_time

    def finish(self, req_id: int) -> None:
        self.active.pop(req_id, None)


# --------------------------------------------------------------------------
# Real KV movement for the in-process multi-engine server
# --------------------------------------------------------------------------
def slice_kv_batch(cache, index: int):
    """Extract request ``index``'s KV slice from a batched cache pytree.
    Cache leaves are [L, B, S, ...] (or [B, ...] for recurrent states with
    leading layer axes folded) — we slice the batch axis (axis 1 for
    [L, B, ...] leaves, axis 0 otherwise is not used here)."""
    return jax.tree.map(lambda a: a[:, index:index + 1], cache)


def merge_kv_batch(cache, piece, index: int):
    """Write a sliced KV piece into slot ``index`` of a batched cache."""
    def put(a, p):
        return jax.lax.dynamic_update_slice_in_dim(a, p.astype(a.dtype),
                                                   index, axis=1)
    return jax.tree.map(put, cache, piece)


def gather_kv_blocks(pool, block_ids):
    """Extract a request's physical blocks from a paged pool.

    Pool leaves are [L, NB, ...] (DESIGN.md §Block pool layout);
    ``block_ids`` is the request's block table (ordered logical->physical).
    Returns leaves [L, nb, ...] in pool layout — the engine turns them into
    the contiguous wire format (``models.attention.blocks_to_piece``), so
    bytes moved scale with ceil(length/BS)·BS, not max_seq.
    """
    idx = jnp.asarray(block_ids, jnp.int32)
    return jax.tree.map(lambda a: a[:, idx], pool)


def scatter_kv_blocks(pool, piece, block_ids):
    """Write pool-layout blocks (leaves [L, nb, ...]) into freshly
    allocated blocks of the destination pool."""
    idx = jnp.asarray(block_ids, jnp.int32)

    def put(a, p):
        return a.at[:, idx].set(p.astype(a.dtype))
    return jax.tree.map(put, pool, piece)


def kv_bytes(cache) -> float:
    return float(sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(cache)))
