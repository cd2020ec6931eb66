"""Prefill attention — Pallas TPU kernels.

Two kernels:

  * :func:`prefill_attention` — whole-prompt causal flash attention over a
    contiguous ``[B, T, ...]`` batch. Standard flash tiling: grid
    ``(B, Hkv, Tq/BQ, S/BK)`` with online-softmax accumulation over the
    innermost (sequential) KV dimension and causal block pruning — upper-
    triangular KV blocks are skipped entirely (``pl.when``), halving
    compute. Used by the monolithic (non-paged) serving path and training.

  * :func:`paged_prefill_attention` — **chunked** prefill over the paged
    KV pool (DESIGN.md §Chunked prefill): a query chunk ``[C]`` of one
    request attends causally to its own chunk plus all previously written
    context, read block-by-block from the pool through a scalar-prefetched
    block table. The grid is a flat work list like
    ``paged_decode_attention_flat`` — cost ∝ chunk × ceil(L_ctx/BS) — so
    serving engines can pack prompt chunks *into* decode iterations
    instead of freezing the batch for a whole long prompt. (The paper's
    §2.1 baseline isolates prefill into dedicated compute-bound
    iterations; chunked prefill is what removes that head-of-line block.)

Block design: q tile [BQ·G, 128], kv tile [BK, 128]; BQ=BK=256 keeps the
working set ≈ (256·G + 2·256) · 128 · 2 B ≲ 1 MB in VMEM with MXU-aligned
contraction dims.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (NEG_INF, _flash_block_update, _flash_finish,
                               _flash_init, flat_work_list)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _prefill_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, bq: int, bk: int):
    b = pl.program_id(0)
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    q_start = i * bq
    kv_start = j * bk

    @pl.when((kv_start <= q_start + bq - 1) & (kv_start < length))
    def _compute():
        G, Dh = q_ref.shape[3], q_ref.shape[4]
        q = q_ref[0, 0].astype(jnp.float32).reshape(bq * G, Dh)
        k = k_ref[0, :, 0].astype(jnp.float32)              # [BK, Dh]
        v = v_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s = s / math.sqrt(Dh)                               # [BQ*G, BK]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((kpos <= qpos) & (kpos < length), s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_ref[:, 0] * alpha + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == nj - 1)
    def _finish():
        G, Dh = o_ref.shape[3], o_ref.shape[4]
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        out = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)
        o_ref[0, 0] = out.reshape(o_ref.shape[2], G, Dh)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def prefill_attention(q, k, v, lengths=None, *, block_q: int = 256,
                      block_k: int = 256, interpret: bool = False):
    """q [B, T, H, Dh]; k, v [B, T, Hkv, Dh] -> [B, T, H, Dh] (causal).

    ``T`` need not be a multiple of the tile sizes: the operands are
    padded internally up to the block multiple and the pad tail is masked
    (kv rows by the ``lengths`` guard, q rows by trimming the output), so
    callers never pre-pad just to satisfy the kernel.
    """
    B, T, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    # pad the sequence to a multiple of both tile sizes; padded kv rows sit
    # at positions >= length (masked in-kernel), padded q rows are trimmed
    block_q = min(block_q, _round_up(T, 8))
    block_k = min(block_k, _round_up(T, 8))
    tile = block_q * block_k // math.gcd(block_q, block_k)
    Tp = _round_up(T, tile)
    if Tp != T:
        pad = ((0, 0), (0, Tp - T), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    lengths = jnp.minimum(lengths, T)
    # [B, Hkv, T, G, Dh] so a q tile is contiguous rows per kv head
    qg = q.reshape(B, Tp, Hkv, G, Dh).transpose(0, 2, 1, 3, 4)

    grid = (B, Hkv, Tp // block_q, Tp // block_k)
    kernel = functools.partial(_prefill_kernel, bq=block_q, bk=block_k)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, G, Dh),
                             lambda b, h, i, j, *p: (b, h, i, 0, 0)),
                pl.BlockSpec((1, block_k, 1, Dh),
                             lambda b, h, i, j, *p: (b, j, h, 0)),
                pl.BlockSpec((1, block_k, 1, Dh),
                             lambda b, h, i, j, *p: (b, j, h, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, G, Dh),
                                   lambda b, h, i, j, *p: (b, h, i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q * G, 128), jnp.float32),
                pltpu.VMEM((block_q * G, 128), jnp.float32),
                pltpu.VMEM((block_q * G, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Tp, G, Dh), q.dtype),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Tp, H, Dh)[:, :T]


# --------------------------------------------------------------------------
# Chunked prefill over the paged pool (DESIGN.md §Chunked prefill)
# --------------------------------------------------------------------------
def _paged_prefill_kernel(wreq_ref, wblk_ref,    # scalar prefetch [W], [W]
                          ctx_ref, clen_ref,     # scalar prefetch [B], [B]
                          bt_ref,                # scalar prefetch [B, NBT]
                          q_ref,                 # [1, 1, C·G, Dh]
                          k_ref, v_ref,          # [1, 1, BS, Dp] (one block)
                          o_ref,                 # [1, 1, C·G, Dh]
                          m_ref, l_ref, acc_ref,   # VMEM scratch
                          *, block_s: int, group: int):
    """Flat-work-list chunked prefill: grid step (h, w) processes work item
    ``w`` = (chunk ``wreq[w]``, logical KV block ``wblk[w]``) — the C
    queries of that chunk against ONE physical pool block holding logical
    rows ``[j·BS, (j+1)·BS)`` of the chunk's request. The work list is the
    Σ_c ceil((ctx_c + clen_c)/BS) real blocks (chunk-major, blocks in
    order) padded to a static bucket; chunk boundaries re-init the
    accumulators and the output row is written on a chunk's last item,
    exactly like ``_flat_paged_kernel``. Causality: query row i (global
    position ctx + i) sees kv position kpos <= ctx + i, so the chunk
    attends to its full written context plus itself, never to unwritten
    pool rows."""
    w = pl.program_id(1)
    nw = pl.num_programs(1)
    c = wreq_ref[w]
    j = wblk_ref[w]
    prev_c = wreq_ref[jnp.maximum(w - 1, 0)]
    next_c = wreq_ref[jnp.minimum(w + 1, nw - 1)]
    first = (w == 0) | (prev_c != c)
    last = (w == nw - 1) | (next_c != c)

    pl.when(first)(lambda: _flash_init(m_ref, l_ref, acc_ref))

    ctx = ctx_ref[c]
    total = ctx + clen_ref[c]
    start = j * block_s

    def _compute():
        rows, dh = q_ref.shape[2], q_ref.shape[3]           # C·G, Dh
        # per-row global query position (row r is chunk token r // G),
        # kept 2-d ([rows, 1], broadcastable) — TPU iota must be >= 2-d
        qpos = ctx + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        _flash_block_update(q_ref[0, 0], k_ref[0, 0, :, 0:dh],
                            v_ref[0, 0, :, 0:dh], m_ref, l_ref, acc_ref,
                            start, total, qpos=qpos)

    pl.when(start < total)(_compute)
    pl.when(last)(lambda: _flash_finish(o_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("num_work", "interpret"))
def paged_prefill_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                            chunk_lens, *, num_work: Optional[int] = None,
                            interpret: bool = False):
    """Chunked causal prefill attention over a paged KV pool.

    q            [B, C, H, Dh]        — B prompt *chunks*, C queries each
                                        (rows past ``chunk_lens[b]`` are
                                        padding; their output is garbage
                                        and must be ignored by the caller)
    k/v_pool     [NB, Hkv, BS, Dp]    — global block pool (head-major, rows
                                        lane-padded to Dp >= Dh). The chunk's own
                                        K/V must ALREADY be scattered into
                                        its blocks (positions ctx..ctx+C)
                                        before this call — partial prompts
                                        live in the pool like decode state
    block_tables [B, NBT] int32       — per-chunk block table covering at
                                        least ceil((ctx+C)/BS) rows
    ctx_lens     [B] int32            — tokens written BEFORE this chunk
    chunk_lens   [B] int32            — real tokens in this chunk
    returns      [B, C, H, Dh]

    Grid ``(Hkv, num_work)`` over the flat (chunk, logical-block) work
    list of Σ_b ceil((ctx_b + chunk_b)/BS) real items — the chunked-
    prefill analogue of :func:`paged_decode_attention_flat`: each work
    item is one [C·G, BS] MXU tile against one pool block, so the cost is
    chunk × context blocks and a chunk never pays another chunk's context
    length. ``num_work`` is a static bucket (callers round to a power of
    two; None = the worst case B·NBT).
    """
    B, C, H, Dh = q.shape
    Hkv, BS, Dp = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]
    G = H // Hkv
    NBT = block_tables.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    W = num_work if num_work is not None else B * NBT
    assert W >= 1
    qg = q.reshape(B, C, Hkv, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, C * G, Dh)
    totals = (ctx_lens + chunk_lens).astype(jnp.int32)
    work_req, work_blk = flat_work_list(totals, NBT, BS, W)

    grid = (Hkv, W)
    kernel = functools.partial(_paged_prefill_kernel, block_s=BS, group=G)

    def q_map(h, w, wreq, wblk, ctx, clen, bt):
        del wblk, ctx, clen, bt
        return (wreq[w], h, 0, 0)

    def kv_map(h, w, wreq, wblk, ctx, clen, bt):
        del ctx, clen
        # padding items carry block index NBT; clamp for the table lookup —
        # whatever block it DMAs is skipped by the kernel's total guard
        return (bt[wreq[w], jnp.minimum(wblk[w], NBT - 1)], h, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, C * G, Dh), q_map),
                pl.BlockSpec((1, 1, BS, Dp), kv_map),
                pl.BlockSpec((1, 1, BS, Dp), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, C * G, Dh), q_map),
            scratch_shapes=[
                pltpu.VMEM((C * G, 128), jnp.float32),   # m (lane-replicated)
                pltpu.VMEM((C * G, 128), jnp.float32),   # l
                pltpu.VMEM((C * G, Dh), jnp.float32),    # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, C * G, Dh), q.dtype),
        name="paged_prefill_attention",
        interpret=interpret,
    )(work_req, work_blk, ctx_lens.astype(jnp.int32),
      chunk_lens.astype(jnp.int32), block_tables, qg, k_pool, v_pool)
    return out.reshape(B, Hkv, C, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, C, H, Dh)
