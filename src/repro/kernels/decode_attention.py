"""Flash-decode GQA attention — Pallas TPU kernel.

This is the serving hot spot the paper's scheduling is built around: decode
attention over a (possibly heterogeneous) batch of KV caches.

TPU adaptation of the paper's SM-block analysis (DESIGN.md §2): the grid is
``(B, Hkv, S/BS)`` and TPU grid steps execute *sequentially* per core, so a
batch padded to its longest member burns ``Σ_b (ceil(maxL/BS) − ceil(L_b/BS))``
wasted block iterations — the TPU restatement of inter-SM imbalance.

Two layouts, three modes, same numerics:
  * ``ragged=False`` (paper-faithful backend): every KV block is fetched and
    computed, out-of-range positions masked — cost ∝ B · ceil(S/BS).
  * ``ragged=True`` (beyond-paper): per-request length scalars are prefetched
    (SMEM) and fully-masked blocks skip the MXU work via ``pl.when`` —
    cost ∝ Σ_b ceil(L_b/BS) plus a small per-skipped-block grid overhead.
  * ``paged_decode_attention``: same ragged skip, but KV lives in a global
    block *pool* ``[NB, Hkv, BS, Dp]`` and each request's blocks are chased
    through a prefetched block table — the serving engine's layout
    (DESIGN.md §Block pool), no per-request padding or copies at all.

Block design for v5e: BS=512 KV rows × Dh=128 lanes (bf16 tile 16×128
aligned, MXU contraction dim 128); the per-(b,hkv) working set is
q [G,128] + k,v [512,128] ≈ 0.26 MB ≪ 16 MB VMEM, leaving room for
double-buffered DMA of the next KV block. Accumulators (m, l, acc) live in
VMEM scratch that persists across the sequential KV-block grid dimension.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the flash core and work-list builder live in kernels.ops (shared with the
# chunked-prefill and fused mixed-iteration kernels); flat_work_list is
# re-exported here for backward compatibility
from repro.kernels.ops import (NEG_INF, _flash_block_update, _flash_finish,
                               _flash_init, flat_work_list)

__all__ = ["decode_attention", "paged_decode_attention",
           "paged_decode_attention_flat", "flat_work_list"]

DEFAULT_BLOCK = 512


def _decode_kernel(lengths_ref,          # scalar prefetch [B]
                   q_ref,                # [1, 1, G, Dh]
                   k_ref, v_ref,         # [1, BS, 1, Dh]
                   o_ref,                # [1, 1, G, Dh]
                   m_ref, l_ref, acc_ref,  # VMEM scratch
                   *, block_s: int, ragged: bool):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    pl.when(j == 0)(lambda: _flash_init(m_ref, l_ref, acc_ref))

    length = lengths_ref[b]
    start = j * block_s

    def _compute():
        _flash_block_update(q_ref[0, 0], k_ref[0, :, 0], v_ref[0, :, 0],
                            m_ref, l_ref, acc_ref, start, length)

    if ragged:
        # skip the MXU work for blocks entirely beyond this request's length
        pl.when(start < length)(_compute)
    else:
        _compute()

    pl.when(j == nj - 1)(lambda: _flash_finish(o_ref, l_ref, acc_ref))


def _paged_block_update(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, start,
                        length):
    """Flash step of a [G, Dh] decode q tile against one head-major pool
    block tile [1, 1, BS, Dp] (lane padding past Dh is never read)."""
    dh = q_ref.shape[-1]
    _flash_block_update(q_ref[0, 0], k_ref[0, 0, :, 0:dh],
                        v_ref[0, 0, :, 0:dh], m_ref, l_ref, acc_ref, start,
                        length)


def _paged_decode_kernel(lengths_ref,        # scalar prefetch [B]
                         bt_ref,             # scalar prefetch [B, NBT]
                         q_ref,              # [1, 1, G, Dh]
                         k_ref, v_ref,       # [1, 1, BS, Dp] (one phys block)
                         o_ref,              # [1, 1, G, Dh]
                         m_ref, l_ref, acc_ref,  # VMEM scratch
                         *, block_s: int):
    """Block-table decode attention: grid step (b, h, j) DMAs *physical*
    block ``bt_ref[b, j]`` (resolved by the index maps below, before the
    body runs — scalar prefetch) holding logical KV rows
    ``[j·BS, (j+1)·BS)`` of request ``b``. Blocks at or beyond the request's
    length are pure padding (tables are padded with block 0) and skip the
    MXU work entirely, so cost is ∝ Σ_b ceil(L_b/BS) — the paged engine
    never pays for another request's length (DESIGN.md §Kernel grid)."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    pl.when(j == 0)(lambda: _flash_init(m_ref, l_ref, acc_ref))

    length = lengths_ref[b]
    start = j * block_s
    pl.when(start < length)(
        lambda: _paged_block_update(q_ref, k_ref, v_ref, m_ref, l_ref,
                                    acc_ref, start, length))
    pl.when(j == nj - 1)(lambda: _flash_finish(o_ref, l_ref, acc_ref))


def _flat_paged_kernel(wreq_ref, wblk_ref,   # scalar prefetch [W], [W]
                       lengths_ref,          # scalar prefetch [B]
                       bt_ref,               # scalar prefetch [B, NBT]
                       q_ref,                # [1, 1, G, Dh]
                       k_ref, v_ref,         # [1, 1, BS, Dp] (one phys block)
                       o_ref,                # [1, 1, G, Dh]
                       m_ref, l_ref, acc_ref,  # VMEM scratch
                       *, block_s: int):
    """Work-flattened paged decode attention: grid step (h, w) processes
    flat work item ``w`` = (request ``wreq[w]``, logical block ``wblk[w]``).
    The work list is exactly the Σ_b ceil(L_b/BS) real blocks (sorted by
    request, blocks in order) padded to a static bucket, so — unlike the
    (B, Hkv, NBT) grid — short requests never burn skipped grid steps up
    to the batch max NBT.

    Request boundaries are detected from the prefetched work list itself:
    the accumulators re-init on the first item of a request and the output
    row is written on its last. Padding items alias the *last* real
    request with sentinel block index NBT (so ``start >= length`` skips
    the MXU work, the accumulators are untouched, and the final write is
    an idempotent re-write of that request's row — never a new row)."""
    w = pl.program_id(1)
    nw = pl.num_programs(1)
    b = wreq_ref[w]
    j = wblk_ref[w]
    prev_b = wreq_ref[jnp.maximum(w - 1, 0)]
    next_b = wreq_ref[jnp.minimum(w + 1, nw - 1)]
    first = (w == 0) | (prev_b != b)
    last = (w == nw - 1) | (next_b != b)

    pl.when(first)(lambda: _flash_init(m_ref, l_ref, acc_ref))

    length = lengths_ref[b]
    start = j * block_s
    pl.when(start < length)(
        lambda: _paged_block_update(q_ref, k_ref, v_ref, m_ref, l_ref,
                                    acc_ref, start, length))
    pl.when(last)(lambda: _flash_finish(o_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("num_work", "interpret"))
def paged_decode_attention_flat(q, k_pool, v_pool, block_tables, lengths, *,
                                num_work: Optional[int] = None,
                                interpret: bool = False):
    """Work-flattened variant of :func:`paged_decode_attention`.

    Same operands, same numerics, different grid: ``(Hkv, num_work)``
    where ``num_work`` is a **static** bucket >= Σ_b ceil(L_b/BS) (callers
    round up to a power of two so recompiles stay O(log total-work); None
    falls back to the worst case B·NBT). The old grid executes
    ``B · Hkv · NBT`` steps and relies on ``pl.when`` to skip the padded
    tail of every short request; this grid executes ``Hkv · num_work``
    steps total — the heterogeneity tax is gone at the grid level, not
    just at the MXU level (DESIGN.md §Decode hot path).
    """
    B, H, Dh = q.shape
    Hkv, BS, Dp = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]
    G = H // Hkv
    NBT = block_tables.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    assert NBT >= 1
    W = num_work if num_work is not None else B * NBT
    assert W >= 1
    qg = q.reshape(B, Hkv, G, Dh)
    work_req, work_blk = flat_work_list(lengths, NBT, BS, W)

    grid = (Hkv, W)
    kernel = functools.partial(_flat_paged_kernel, block_s=BS)

    def q_map(h, w, wreq, wblk, lens, bt):
        del wblk, lens, bt
        return (wreq[w], h, 0, 0)

    def kv_map(h, w, wreq, wblk, lens, bt):
        del lens
        # padding items carry block index NBT; clamp for the table lookup —
        # whatever block it DMAs is skipped by the kernel's length guard
        return (bt[wreq[w], jnp.minimum(wblk[w], NBT - 1)], h, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, Dh), q_map),
                pl.BlockSpec((1, 1, BS, Dp), kv_map),
                pl.BlockSpec((1, 1, BS, Dp), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, Dh), q_map),
            scratch_shapes=[
                pltpu.VMEM((G, 128), jnp.float32),   # m (lane-replicated)
                pltpu.VMEM((G, 128), jnp.float32),   # l
                pltpu.VMEM((G, Dh), jnp.float32),    # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        name="paged_decode_attention_flat",
        interpret=interpret,
    )(work_req, work_blk, lengths, block_tables, qg, k_pool, v_pool)
    return out.reshape(B, H, Dh)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           interpret: bool = False):
    """Decode attention over a paged KV pool.

    q            [B, H, Dh]              — one query token per request
    k/v_pool     [NB, Hkv, BS, Dp]       — global physical block pool
                                           (head-major, rows lane-padded
                                           to Dp >= Dh)
    block_tables [B, NBT] int32          — physical block id per logical
                                           block; rows past a request's
                                           ceil(L_b/BS) blocks are padding
    lengths      [B] int32               — valid tokens per request
    returns      [B, H, Dh]

    TPU mapping: both scalars are prefetched (SMEM) so the KV BlockSpec
    index maps can chase the block table — grid step (b, h, j) DMAs
    physical block ``block_tables[b, j]`` from HBM while step j−1 computes
    (standard double-buffered sequential grid). Fully padded steps skip
    the MXU via ``pl.when``; the paged pool means no request is ever
    padded to another's length, so the grid cost is Σ_b ceil(L_b/BS).
    """
    B, H, Dh = q.shape
    Hkv, BS, Dp = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]
    G = H // Hkv
    NBT = block_tables.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    qg = q.reshape(B, Hkv, G, Dh)

    grid = (B, Hkv, NBT)
    kernel = functools.partial(_paged_decode_kernel, block_s=BS)

    def kv_map(b, h, j, lens, bt):
        del lens
        return (bt[b, j], h, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, Dh), lambda b, h, j, *pf: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, BS, Dp), kv_map),
                pl.BlockSpec((1, 1, BS, Dp), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, Dh),
                                   lambda b, h, j, *pf: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 128), jnp.float32),   # m (lane-replicated)
                pltpu.VMEM((G, 128), jnp.float32),   # l
                pltpu.VMEM((G, Dh), jnp.float32),    # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        name="paged_decode_attention",
        interpret=interpret,
    )(lengths, block_tables, qg, k_pool, v_pool)
    return out.reshape(B, H, Dh)


@functools.partial(jax.jit, static_argnames=("block_s", "ragged", "interpret"))
def decode_attention(q, k, v, lengths, *, block_s: int = DEFAULT_BLOCK,
                     ragged: bool = False, interpret: bool = False):
    """q [B, H, Dh]; k, v [B, S, Hkv, Dh]; lengths [B] int32 -> [B, H, Dh].

    ``interpret=True`` runs the kernel body in Python on CPU (used for all
    validation in this repo); on a real TPU leave it False.
    """
    B, H, Dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    assert H % Hkv == 0, (H, Hkv)
    # monolithic caches come in any size: clamp the block to the sequence
    # and pad the sequence up to a whole number of blocks (padded rows are
    # masked by the length guard, which never exceeds S)
    block_s = min(block_s, S)
    nj = -(-S // block_s)
    if nj * block_s != S:
        pad = ((0, 0), (0, nj * block_s - S), (0, 0), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    qg = q.reshape(B, Hkv, G, Dh)

    grid = (B, Hkv, nj)
    kernel = functools.partial(_decode_kernel, block_s=block_s, ragged=ragged)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, Dh), lambda b, h, j, *prefetch: (b, h, 0, 0)),
                pl.BlockSpec((1, block_s, 1, Dh), lambda b, h, j, *prefetch: (b, j, h, 0)),
                pl.BlockSpec((1, block_s, 1, Dh), lambda b, h, j, *prefetch: (b, j, h, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, Dh), lambda b, h, j, *prefetch: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 128), jnp.float32),   # m (lane-replicated)
                pltpu.VMEM((G, 128), jnp.float32),   # l
                pltpu.VMEM((G, Dh), jnp.float32),    # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(B, H, Dh)
