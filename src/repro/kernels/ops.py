"""Jitted public wrappers for the Pallas kernels, plus the ONE shared
flash-attention inner core every paged kernel builds on.

Off a TPU the kernels run with ``interpret=True`` (Python execution of
the kernel body); on a TPU they always compile. The
``backend`` argument lets callers (engine, tests) pick:

  * ``"xla"``     — pure-jnp reference (fast on CPU, default here)
  * ``"pallas"``  — the TPU kernel (interpret on CPU, compiled on TPU)

The ``_flash_*`` helpers below are the online-softmax KV-block core shared
by the decode, chunked-prefill, AND fused mixed-iteration kernels — one
implementation, imported by all three (no cross-module private imports).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Shared flash-attention core (decode / chunked prefill / fused mixed)
# --------------------------------------------------------------------------
def _flash_block_update(q, k, v, m_ref, l_ref, acc_ref, start, length,
                        qpos=None, k_scale=None, v_scale=None):
    """ONE online-softmax KV-block step, shared by the decode kernels, the
    chunked-prefill kernel AND the fused mixed-iteration kernel: the q
    rows ``q`` [rows, Dh] ([G, Dh] for a decode row, [C·G, Dh] for a
    prefill chunk) vs. this grid step's KV block ``k``/``v`` [BS, Dh],
    masked at ``length``, accumulated into the FIRST ``rows`` rows of the
    persistent (m, l, acc) scratch. Callers load the tiles from their
    refs, so the core is independent of the operand layouts.

    ``qpos`` (per-row global query positions) additionally applies the
    causal ``kv <= q`` mask of chunked prefill; decode's single query row
    needs none. ``k_scale``/``v_scale`` ([1, BS], f32, one per KV row)
    dequantize an int8 KV block: they scale the score columns and the
    probability columns, which equals scaling the K/V rows, so the pool
    stays int8 in HBM and DMA bytes halve (DESIGN.md §Quantized KV
    blocks)."""
    rows = q.shape[0]
    sl = slice(0, rows)
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)                       # [BS, Dh]
    v = v.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [rows, BS]
    if k_scale is not None:
        s = s * k_scale
    s = s / math.sqrt(q.shape[-1])
    idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = idx < length
    if qpos is not None:                 # qpos broadcastable to [rows, BS]
        keep &= idx <= qpos
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_ref[sl, 0:1]                         # [rows, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                          # [rows, BS]
    l_new = l_ref[sl, 0:1] * alpha + p.sum(axis=-1, keepdims=True)
    pv = p if v_scale is None else p * v_scale
    acc_ref[sl, :] = (acc_ref[sl, :] * alpha
                      + jax.lax.dot_general(pv, v, (((1,), (0,)), ((), ()))))
    m_ref[sl, :] = jnp.broadcast_to(m_new, (rows, m_ref.shape[1]))
    l_ref[sl, :] = jnp.broadcast_to(l_new, (rows, l_ref.shape[1]))


def _flash_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _flash_finish(o_ref, l_ref, acc_ref):
    """Normalize the accumulators into the [rows, Dh] output tile
    ``o_ref[0, 0]``."""
    l = l_ref[:, 0:1]
    safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def work_blocks(lengths, block_s: int):
    """Work items per request of the flattened grids: ``ceil(L_b/BS)``
    blocks each, 0 for an empty (dead) request. int32 ``[B]``."""
    return jnp.maximum(-(-lengths // block_s), 0).astype(jnp.int32)


def flat_work_list(lengths, nbt: int, block_s: int, num_work: int):
    """Flat (request, logical block) work list for the flattened grids —
    pure jnp, so the serving engine builds it on device every step.

    Items ``[0, Σ_b ceil(L_b/BS))`` enumerate every request's real blocks
    (request-major, blocks in order); the tail up to ``num_work`` is
    padding aliasing the last request with ``nbt`` (one past the table) as
    its block index, which the kernels' ``start < length`` guard always
    skips. ``num_work`` is the list's static length: the flat decode and
    prefill kernels step through all of it (a bucket the caller picks,
    ``>= Σ_b ceil(L_b/BS)``); the fused mixed kernel passes the worst
    case ``B·NBT`` and steps through the real items only.
    Returns int32 ``(work_req [num_work], work_blk [num_work])``."""
    B = lengths.shape[0]
    nb = work_blocks(lengths, block_s)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(nb)])
    total = offs[-1]
    w = jnp.arange(num_work, dtype=jnp.int32)
    b = jnp.clip(jnp.searchsorted(offs, w, side="right") - 1, 0, B - 1)
    b = b.astype(jnp.int32)
    j = w - offs[b]
    # last request with any real work (argmax of reversed has-work mask);
    # padding must alias it so the output index map never leaves its row
    last_b = (B - 1 - jnp.argmax((nb > 0)[::-1])).astype(jnp.int32)
    pad = w >= total
    return (jnp.where(pad, last_b, b),
            jnp.where(pad, jnp.int32(nbt), j))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def decode_attention(q, k, v, lengths, *, backend: str = "xla",
                     ragged: bool = False, block_s: int = 512):
    from repro.kernels import ref
    if backend == "xla":
        return ref.decode_attention_ref(q, k, v, lengths)
    if backend == "pallas":
        from repro.kernels.decode_attention import (
            decode_attention as _decode_pallas)
        return _decode_pallas(q, k, v, lengths, block_s=block_s,
                              ragged=ragged, interpret=not _on_tpu())
    raise ValueError(f"unknown backend {backend!r}")


def prefill_attention(q, k, v, lengths=None, *, backend: str = "xla",
                      block_q: int = 256, block_k: int = 256):
    from repro.kernels import ref
    if backend == "xla":
        return ref.prefill_attention_ref(q, k, v, lengths)
    if backend == "pallas":
        from repro.kernels.prefill_attention import (
            prefill_attention as _prefill_pallas)
        return _prefill_pallas(q, k, v, lengths, block_q=block_q,
                               block_k=block_k, interpret=not _on_tpu())
    raise ValueError(f"unknown backend {backend!r}")
