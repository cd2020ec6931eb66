"""Fused mixed-iteration attention — ONE Pallas launch per mixed step.

PR 4's mixed iterations still issue two flat-grid launches per layer: the
decode work list (``paged_decode_attention_flat``) and the prefill-chunk
work list (``paged_prefill_attention``). Each pays its own pow2 padding
and launch overhead — exactly the double cost ROADMAP item 2 targets.

:func:`paged_mixed_attention` packs *all* (segment, logical-block) items
of a mixed iteration into a single scalar-prefetched work list: a
*segment* is either a decode row (qlen = 1, ``tag = 0``) or a prefill
chunk (qlen = chunk, ``tag = 1``), interleaved freely. One grid
``(Hkv, W)`` where ``W >= Σ_s ceil((ctx_s + seg_s)/BS)`` is the caller's
static work bucket. The engine picks ``W = pow2(decode items) +
pow2(chunk items)`` — split buckets, because a single pow2 of the sum
can overshoot the pair (9+8 → 32 vs 16+8) and make the merged grid pad
MORE than the two kernels it replaces; split, the padding tail matches
the separate launches exactly and fusion's win is the saved launch.

Work-list layout (DESIGN.md §Fused mixed-iteration attention): segment
``s`` contributes ``ceil(total_s/BS)`` consecutive items where
``total_s = ctx_s + seg_s`` (for decode, ctx = L−1 and seg = 1, so
total = L — a decode row IS a chunk of length 1). Tag encoding is a
prefetched int32 vector indexed by segment: 0 → narrow [G, BS] update on
the q tile's first chunk row, 1 → full [C·G, BS] causally-masked update.
The same garbage-block/sentinel discipline as the other flat grids
applies: padding items alias the last real segment with block index NBT,
the ``start < total`` guard skips them, and the final write is an
idempotent re-write of that segment's row.

Quantized KV (``k_scale``/``v_scale`` given): the pool is int8 with f32
per-(block, kv-head, position) scales; blocks are dequantized in-register
inside the shared flash core, so HBM DMA moves ~half the bytes
(DESIGN.md §Quantized KV blocks).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (_flash_block_update, _flash_finish,
                               _flash_init, flat_work_list)


def _mixed_kernel(wreq_ref, wblk_ref,     # scalar prefetch [W], [W]
                  tags_ref,               # scalar prefetch [B]
                  ctx_ref, slen_ref,      # scalar prefetch [B], [B]
                  bt_ref,                 # scalar prefetch [B, NBT]
                  q_ref,                  # [1, 1, C·G, Dh]
                  k_ref, v_ref,           # [1, 1, BS, Dp] (one phys block)
                  *rest,                  # (+ks,vs if quantized) o, scratch
                  block_s: int, group: int, quantized: bool):
    """Grid step (h, w): flat work item ``w`` = (segment ``wreq[w]``,
    logical KV block ``wblk[w]``) against kv head ``h`` of ONE physical
    pool block. Segment boundaries re-init the accumulators / write the
    output row exactly like ``_flat_paged_kernel``; the per-segment tag
    picks the decode or chunk compute shape against the SAME scratch and
    KV DMA."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    h = pl.program_id(0)
    w = pl.program_id(1)
    nw = pl.num_programs(1)
    s = wreq_ref[w]
    j = wblk_ref[w]
    prev_s = wreq_ref[jnp.maximum(w - 1, 0)]
    next_s = wreq_ref[jnp.minimum(w + 1, nw - 1)]
    first = (w == 0) | (prev_s != s)
    last = (w == nw - 1) | (next_s != s)

    pl.when(first)(lambda: _flash_init(m_ref, l_ref, acc_ref))

    ctx = ctx_ref[s]
    total = ctx + slen_ref[s]
    start = j * block_s
    is_chunk = tags_ref[s] == 1

    def _update(rows, qpos=None):
        if quantized:
            # the scale block holds every kv head's [BS] row; pick head h
            # with a masked sum (no dynamic sublane slice)
            hsel = (jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape[1:], 0)
                    == h)
            k_scale = jnp.sum(jnp.where(hsel, ks_ref[0], 0.0), axis=0,
                              keepdims=True)          # [1, BS]
            v_scale = jnp.sum(jnp.where(hsel, vs_ref[0], 0.0), axis=0,
                              keepdims=True)
        else:
            k_scale = v_scale = None
        dh = q_ref.shape[3]          # pool rows may carry lane padding
        _flash_block_update(q_ref[0, 0, 0:rows], k_ref[0, 0, :, 0:dh],
                            v_ref[0, 0, :, 0:dh], m_ref, l_ref, acc_ref,
                            start, total, qpos=qpos,
                            k_scale=k_scale, v_scale=v_scale)

    def _chunk():
        rows = q_ref.shape[2]                       # C·G
        # per-row global query position (row r is chunk token r // G),
        # kept 2-d ([rows, 1], broadcastable) — TPU iota must be >= 2-d
        qpos = ctx + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        _update(rows, qpos)

    def _decode():
        # qlen = 1: only the first chunk row's G heads are live, so pay a
        # [G, BS] MXU tile instead of [C·G, BS]; the decode length mask
        # (idx < total, total = L) IS the causal mask at qpos = L−1
        _update(group)

    def _compute():
        pl.when(is_chunk)(_chunk)
        pl.when(jnp.logical_not(is_chunk))(_decode)

    pl.when(start < total)(_compute)
    pl.when(last)(lambda: _flash_finish(o_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("num_work", "interpret"))
def paged_mixed_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                          seg_lens, tags, k_scale=None, v_scale=None, *,
                          num_work: Optional[int] = None,
                          interpret: bool = False):
    """Fused mixed-iteration attention over a paged KV pool.

    q            [B, C, H, Dh]     — B *segments*, C query rows each. A
                                     chunk segment uses rows [0, seg) and
                                     a decode segment row 0 only; rows
                                     past ``seg_lens[s]`` are padding
                                     whose output the caller must ignore
    k/v_pool     [NB, Hkv, BS, Dp] — global block pool, head-major, rows
                                     lane-padded to Dp >= Dh (bf16/f32, or
                                     int8 with ``k_scale``/``v_scale``).
                                     Every segment's own K/V must ALREADY
                                     be scattered before this call
    block_tables [B, NBT] int32    — per-segment block table covering at
                                     least ceil((ctx+seg)/BS) rows
    ctx_lens     [B] int32         — tokens before this segment's queries
                                     (decode: L−1; chunk: written context)
    seg_lens     [B] int32         — query rows (decode: 1; chunk: clen)
    tags         [B] int32         — 0 = decode row, 1 = prefill chunk
    k/v_scale    [NB, Hkv, BS] f32 — per-(block, kv-head, position) int8
                                     dequant scales (both or neither)
    returns      [B, C, H, Dh]

    Grid ``(Hkv, num_work)`` over the flat (segment, logical-block) work
    list of Σ_s ceil((ctx_s + seg_s)/BS) real items — ONE launch covers
    the whole mixed iteration. ``num_work`` is a static bucket (callers
    round to a power of two; None = the worst case B·NBT).

    TPU tiling (DESIGN.md §Block pool layout): a KV block is the
    contiguous [BS, Dp] slab of one kv head, and the q/o tile is
    [C·G, Dh] (row ``c·G + g`` = chunk token c, group head g), so every
    block's last two dims are whole array dims.
    """
    B, C, H, Dh = q.shape
    Hkv, BS, Dp = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]
    G = H // Hkv
    assert Dp >= Dh, (Dp, Dh)
    NBT = block_tables.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    assert (k_scale is None) == (v_scale is None)
    quantized = k_scale is not None
    W = num_work if num_work is not None else B * NBT
    assert W >= 1
    qg = q.reshape(B, C, Hkv, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, C * G, Dh)
    totals = (ctx_lens + seg_lens).astype(jnp.int32)
    work_req, work_blk = flat_work_list(totals, NBT, BS, W)

    grid = (Hkv, W)
    kernel = functools.partial(_mixed_kernel, block_s=BS, group=G,
                               quantized=quantized)

    def q_map(h, w, wreq, wblk, tags, ctx, slen, bt):
        del wblk, tags, ctx, slen, bt
        return (wreq[w], h, 0, 0)

    def _blk(w, wreq, wblk, bt):
        # padding items carry block index NBT; clamp for the table lookup —
        # whatever block it DMAs is skipped by the kernel's total guard
        return bt[wreq[w], jnp.minimum(wblk[w], NBT - 1)]

    def kv_map(h, w, wreq, wblk, tags, ctx, slen, bt):
        del tags, ctx, slen
        return (_blk(w, wreq, wblk, bt), h, 0, 0)

    def scale_map(h, w, wreq, wblk, tags, ctx, slen, bt):
        del h, tags, ctx, slen
        return (_blk(w, wreq, wblk, bt), 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, C * G, Dh), q_map),
        pl.BlockSpec((1, 1, BS, Dp), kv_map),
        pl.BlockSpec((1, 1, BS, Dp), kv_map),
    ]
    operands = [qg, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, Hkv, BS), scale_map),
                     pl.BlockSpec((1, Hkv, BS), scale_map)]
        operands += [k_scale, v_scale]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, C * G, Dh), q_map),
            scratch_shapes=[
                pltpu.VMEM((C * G, 128), jnp.float32),   # m (lane-replicated)
                pltpu.VMEM((C * G, 128), jnp.float32),   # l
                pltpu.VMEM((C * G, Dh), jnp.float32),    # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, C * G, Dh), q.dtype),
        name="paged_mixed_attention",
        interpret=interpret,
    )(work_req, work_blk, tags.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      seg_lens.astype(jnp.int32), block_tables, *operands)
    return out.reshape(B, Hkv, C, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, C, H, Dh)
