"""Fused mixed-iteration attention — ONE Pallas launch per mixed step.

PR 4's mixed iterations still issue two flat-grid launches per layer: the
decode work list (``paged_decode_attention_flat``) and the prefill-chunk
work list (``paged_prefill_attention``). Each pays its own pow2 padding
and launch overhead — exactly the double cost ROADMAP item 2 targets.

:func:`paged_mixed_attention` packs *all* (segment, logical-block) items
of a mixed iteration into a single scalar-prefetched work list: a
*segment* is either a decode row (qlen = 1, ``tag = 0``) or a prefill
chunk (qlen = chunk, ``tag = 1``), interleaved freely. One grid
``(Hkv, n)`` where ``n = Σ_s ceil((ctx_s + seg_s)/BS)`` is computed on
the device from the call's lengths: a dynamic grid bound, so the kernel
steps through the real items only and one compiled program serves every
batch make-up of the same shapes. The work list itself keeps a static
capacity from the shapes alone, ``B·NBT`` (the exact worst case).

Work-list layout (DESIGN.md §Fused mixed-iteration attention): segment
``s`` contributes ``ceil(total_s/BS)`` consecutive items where
``total_s = ctx_s + seg_s`` (for decode, ctx = L−1 and seg = 1, so
total = L — a decode row IS a chunk of length 1). Tag encoding is a
prefetched int32 vector indexed by segment: 0 → narrow [G, BS] update on
the q tile's first chunk row, 1 → full [C·G, BS] causally-masked update.
Segments with total 0 (dead slots) contribute no items. A call with no
items still launches one grid step: the capacity tail's padding item
aliases the last segment with block index NBT, so the ``start < total``
guard skips its compute and it writes that dead segment's row only.

Quantized KV (``k_scale``/``v_scale`` given): the pool is int8 with f32
per-(block, kv-head, position) scales; blocks are dequantized in-register
inside the shared flash core, so HBM DMA moves ~half the bytes
(DESIGN.md §Quantized KV blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import (_flash_block_update, _flash_finish,
                               _flash_init, flat_work_list, work_blocks)

# a work item packs (segment, logical block) into one int32
_SEG_SHIFT, _BLK_MASK = 16, 0xFFFF

# The work list and the block table sit in SMEM, B·NBT int32 entries each;
# a v5e's 1 MiB holds both up to B·NBT = 129,024 (63 x 2,048) and refuses
# 131,072. This limit keeps 16 KiB of room; callers size B and NBT under it.
MAX_WORK_ITEMS = 2 ** 17 - 2 ** 12


def _mixed_kernel(work_ref,               # scalar prefetch [B·NBT]
                  tags_ref,               # scalar prefetch [B]
                  ctx_ref, slen_ref,      # scalar prefetch [B], [B]
                  bt_ref,                 # scalar prefetch [B, NBT]
                  q_ref,                  # [1, 1, C·G, Dh]
                  k_ref, v_ref,           # [1, 1, BS, Dp] (one phys block)
                  *rest,                  # (+ks,vs if quantized) o, scratch
                  block_s: int, group: int, quantized: bool):
    """Grid step (h, w), ``w < n``: flat work item ``w`` = (segment
    ``work[w] >> 16``, logical KV block ``work[w] & 0xFFFF``) against kv
    head ``h`` of ONE physical pool block. Segment boundaries re-init the
    accumulators / write the output row exactly like
    ``_flat_paged_kernel``; the per-segment tag picks the decode or chunk
    compute shape against the SAME scratch and KV DMA."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    h = pl.program_id(0)
    w = pl.program_id(1)
    nw = pl.num_programs(1)
    s = work_ref[w] >> _SEG_SHIFT
    j = work_ref[w] & _BLK_MASK
    prev_s = work_ref[jnp.maximum(w - 1, 0)] >> _SEG_SHIFT
    next_s = work_ref[jnp.minimum(w + 1, nw - 1)] >> _SEG_SHIFT
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


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_mixed_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                          seg_lens, tags, k_scale=None, v_scale=None, *,
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
    returns      [B, C, H, Dh]     — rows of segments with total 0 are
                                     garbage

    Grid ``(Hkv, n)`` over the flat (segment, logical-block) work list,
    ``n = Σ_s ceil((ctx_s + seg_s)/BS)`` real items counted on the device
    (at least one step) — ONE launch covers the whole mixed iteration,
    and calls that differ only in their lengths share one program. The
    prefetched work list holds ``B·NBT`` entries, the most ``n`` can be,
    one int32 each (segment in the high 16 bits, block in the low 16): it
    shares the 1 MiB of SMEM with the ``[B, NBT]`` table, so ``B·NBT``
    may not pass ``MAX_WORK_ITEMS``.

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
    assert B < 2 ** 15 and NBT <= _BLK_MASK, (B, NBT)
    assert B * NBT <= MAX_WORK_ITEMS, \
        f"{B} segments x {NBT} table columns overflow the work list's SMEM"
    qg = q.reshape(B, C, Hkv, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, C * G, Dh)
    totals = (ctx_lens + seg_lens).astype(jnp.int32)
    work_req, work_blk = flat_work_list(totals, NBT, BS, B * NBT)
    work = (work_req << _SEG_SHIFT) | work_blk
    # the grid bound is the real item count, a traced scalar; a call with
    # no items still runs one step, which the total guard skips
    n = jnp.maximum(jnp.sum(work_blocks(totals, BS)), 1)

    grid = (Hkv, n)
    kernel = functools.partial(_mixed_kernel, block_s=BS, group=G,
                               quantized=quantized)

    def q_map(h, w, work, tags, ctx, slen, bt):
        del tags, ctx, slen, bt
        return (work[w] >> _SEG_SHIFT, h, 0, 0)

    def _blk(w, work, bt):
        # the padding item of an empty call carries block index NBT; clamp
        # for the table lookup — the kernel's total guard skips its block
        return bt[work[w] >> _SEG_SHIFT,
                  jnp.minimum(work[w] & _BLK_MASK, NBT - 1)]

    def kv_map(h, w, work, tags, ctx, slen, bt):
        del tags, ctx, slen
        return (_blk(w, work, bt), h, 0, 0)

    def scale_map(h, w, work, tags, ctx, slen, bt):
        del h, tags, ctx, slen
        return (_blk(w, work, bt), 0, 0)

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
            num_scalar_prefetch=5,
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
    )(work, tags.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      seg_lens.astype(jnp.int32), block_tables, *operands)
    return out.reshape(B, Hkv, C, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, C, H, Dh)
