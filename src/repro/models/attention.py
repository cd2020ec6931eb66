"""GQA attention with RoPE / M-RoPE, full and sliding-window variants,
prefill and single-token decode against a preallocated KV cache.

Shapes follow the serving convention:
  activations  x        [B, T, D]
  kv cache     k, v     [B, S, H_kv, Dh]   (ring buffer of size W when
                                            sliding_window > 0)
The decode step writes ONE token at ``pos`` and attends over the cache —
this is what ``serve_step`` lowers in the multi-pod dry-run.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, dense_init, psum_if_tp

NEG_INF = -1e30

# Paged decode attention backends (DESIGN.md §Decode hot path):
#   dense — XLA gather of pool[block_tables] + masked SDPA. Materializes a
#           [B, NBT·BS, Hkv, Dh] copy per layer per step; CPU/debug fallback.
#   grid  — Pallas kernel, grid (B, Hkv, NBT): no gather, but every request
#           pays max-NBT grid steps (skipped blocks still cost a grid step).
#   flat  — Pallas kernel over a flat work list of Σ_b ceil(L_b/BS) items:
#           no gather AND no per-request padding at the grid level.
#   fused — Pallas kernel over ONE tagged work list covering decode rows
#           AND prefill chunks of a mixed iteration: single launch per
#           layer per step (DESIGN.md §Fused mixed-iteration attention).
PAGED_BACKENDS = ("dense", "grid", "flat", "fused")

# KV block-pool storage layouts (DESIGN.md §Quantized KV blocks):
#   bf16 — the model dtype, full-width rows.
#   int8 — symmetric per-(block, kv-head, position) int8 with f32 row
#          scales; quantize-on-write, dequant in-register inside the
#          flash core. Supported by the "fused" and "dense" backends.
KV_DTYPES = ("bf16", "int8")

# TPU vector lanes. Pool rows are padded to a multiple of this so the TPU
# keeps the pool row-major: with a narrower minor dim its default layout
# moves the block axis into the lanes, and every kernel call would then
# copy the whole pool into the layout the kernel reads (DESIGN.md §Block
# pool layout).
LANES = 128


def pool_row_width(head_dim: int) -> int:
    """Lane width ``Dp`` of one pool row: ``head_dim`` rounded up to
    :data:`LANES`; lanes past ``head_dim`` hold zeros and are never read."""
    return -(-head_dim // LANES) * LANES


def resolve_paged_backend(backend: Optional[str] = None):
    """(backend, interpret) for this process. An explicit backend wins,
    else auto: the fused Pallas kernel on TPU, the dense XLA path
    elsewhere (Pallas off-TPU would need interpret mode, which is for
    validation, not speed). Asking for a kernel backend off-TPU gets
    interpret=True so it still runs; on TPU a kernel is always compiled."""
    choice = backend or "auto"
    on_tpu = jax.default_backend() == "tpu"
    if choice == "auto":
        choice = "fused" if on_tpu else "dense"
    assert choice in PAGED_BACKENDS, f"unknown paged backend {choice!r}"
    return choice, (choice != "dense" and not on_tpu)


# --------------------------------------------------------------------------
# Rotary embeddings
# --------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x [B, T, H, Dh]; positions [B, T] (int)."""
    freqs = rope_freqs(x.shape[-1], theta)                       # [Dh/2]
    angles = positions[..., None].astype(jnp.float32) * freqs    # [B, T, Dh/2]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections=(2, 3, 3)):
    """Qwen2-VL M-RoPE: head_dim/2 frequency slots split into (t, h, w)
    sections, each rotated by its own position stream.

    x [B, T, H, Dh]; positions3 [B, T, 3] (temporal, height, width ids —
    identical streams for pure text).  [arXiv:2409.12191]
    """
    dh = x.shape[-1]
    half = dh // 2
    freqs = rope_freqs(dh, theta)                                # [half]
    total = sum(sections)
    bounds = []
    start = 0
    for s in sections:
        n = (half * s) // total
        bounds.append((start, start + n))
        start += n
    bounds[-1] = (bounds[-1][0], half)  # absorb rounding into last section
    pos = positions3.astype(jnp.float32)                         # [B, T, 3]
    angle_parts = []
    for i, (lo, hi) in enumerate(bounds):
        angle_parts.append(pos[..., i:i + 1] * freqs[lo:hi])     # [B, T, hi-lo]
    angles = jnp.concatenate(angle_parts, axis=-1)               # [B, T, half]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, cross: bool = False):
    d, h, hk, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (d, h * dh), cfg.dtype),
        "wk": dense_init(ks[1], (d, hk * dh), cfg.dtype),
        "wv": dense_init(ks[2], (d, hk * dh), cfg.dtype),
        "wo": dense_init(ks[3], (h * dh, d), cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), cfg.dtype)
        p["bk"] = jnp.zeros((hk * dh,), cfg.dtype)
        p["bv"] = jnp.zeros((hk * dh,), cfg.dtype)
    return p


def _project_qkv(p, cfg: ModelConfig, x):
    B, T, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    # head counts come from the projection widths, NOT cfg: under serving
    # tensor parallelism (DESIGN.md §Sharded serving) the local wq/wk/wv
    # shards hold H/TP and Hkv/TP heads, and the contiguous output-dim
    # split keeps each shard's q heads aligned with its own kv heads (GQA
    # group size G = H/Hkv is shard-invariant).
    dh = cfg.head_dim
    h, hk = q.shape[-1] // dh, k.shape[-1] // dh
    return (q.reshape(B, T, h, dh), k.reshape(B, T, hk, dh),
            v.reshape(B, T, hk, dh))


# --------------------------------------------------------------------------
# Core SDPA (GQA, masked) — the XLA path. jnp.einsum lets GSPMD shard the
# KV sequence axis for context-parallel long decode.
# --------------------------------------------------------------------------
def _gqa_sdpa(q, k, v, mask):
    """q [B,Tq,H,Dh]; k,v [B,S,Hkv,Dh]; mask broadcastable to
    [B, Hkv, G, Tq, S] (pass 5-d masks; None = attend everything).

    K/V stay in their storage dtype — f32 accumulation comes from
    ``preferred_element_type`` so the (multi-GiB in decode) cache is never
    materialized as an f32 copy; scores/softmax still run in f32.
    """
    B, Tq, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Tq, Hkv, G, Dh)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(Dh).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgts,bshd->bthgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Tq, H, Dh).astype(q.dtype)


def _causal_mask(Tq: int, S: int, q_offset, window: int = 0):
    """[1, 1, 1, Tq, S] boolean; True = attend. q position i (global
    q_offset + i) may see kv position j <= its own; window limits lookback."""
    qpos = q_offset + jnp.arange(Tq)[:, None]
    kpos = jnp.arange(S)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    return m[None, None, None]


# --------------------------------------------------------------------------
# Memory-bounded flash attention (XLA path): double lax.scan over q / kv
# blocks with online softmax. This is what long-sequence prefill/train
# lower to on the production mesh — peak temp is O(BQ·BK) per chip instead
# of O(T·S). (The Pallas kernel is the TPU-executed equivalent; this is
# the pjit-shardable formulation. Causal block pruning is NOT applied —
# the grid is static — so HLO FLOPs count ~2× the causal minimum; the
# roofline's useful_flops_ratio surfaces that.)
# --------------------------------------------------------------------------
FLASH_THRESHOLD = 2048 * 2048   # T·S above which prefill uses the scan path


def flash_attention_xla(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = 512, block_k: int = 1024):
    """q [B,T,H,Dh]; k,v [B,S,Hkv,Dh] -> [B,T,H,Dh]."""
    B, T, H, Dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    bq, bk = min(block_q, T), min(block_k, S)
    nq, nk = -(-T // bq), -(-S // bk)
    Tp, Sp = nq * bq, nk * bk
    qf = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    kf = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    qg = qf.reshape(B, nq, bq, Hkv, G, Dh).astype(jnp.float32)
    kg = kf.reshape(B, nk, bk, Hkv, Dh).astype(jnp.float32)
    vg = vf.reshape(B, nk, bk, Hkv, Dh).astype(jnp.float32)
    scale = 1.0 / math.sqrt(Dh)

    def q_step(_, qi):
        qblk, i = qi                      # [B,bq,Hkv,G,Dh], scalar
        qpos = i * bq + jnp.arange(bq)

        @jax.checkpoint   # backward recomputes p per block (flash-style):
        def kv_step(carry, kvj):          # else AD saves every [bq,bk] tile
            m, l, acc = carry
            kblk, vblk, j = kvj
            kpos = j * bk + jnp.arange(bk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk) * scale
            mask = kpos[None, :] < S      # padding
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, bq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, bq, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.swapaxes(kg, 0, 1), jnp.swapaxes(vg, 0, 1),
             jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # [B,Hkv,G,bq,Dh]
        return None, out

    _, outs = jax.lax.scan(jax.checkpoint(q_step), None,
                           (jnp.swapaxes(qg, 0, 1), jnp.arange(nq)))
    # outs [nq, B, Hkv, G, bq, Dh] -> [B, T, H, Dh]
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 1, 4, 2, 3, 5)
    out = out.reshape(B, Tp, H, Dh)[:, :T]
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# Prefill: full self-attention over the prompt, returns the populated cache.
# --------------------------------------------------------------------------
def attention_prefill(p, cfg: ModelConfig, x, positions, *, mrope_positions=None):
    q, k, v = _project_qkv(p, cfg, x)
    if cfg.use_mrope:
        q = apply_mrope(q, mrope_positions, cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.rope_theta)
    elif not cfg.learned_pos:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    T = x.shape[1]
    B = x.shape[0]
    if T * T > FLASH_THRESHOLD:
        out = flash_attention_xla(q, k, v, causal=True,
                                  window=cfg.sliding_window)
    else:
        mask = _causal_mask(T, T, 0, cfg.sliding_window)
        out = _gqa_sdpa(q, k, v, mask)
    return psum_if_tp(out.reshape(B, T, -1) @ p["wo"], cfg), (k, v)


# --------------------------------------------------------------------------
# Decode: one token vs. a preallocated cache.
# --------------------------------------------------------------------------
class KVCache(NamedTuple):
    k: jnp.ndarray  # [B, S, Hkv, Dh]
    v: jnp.ndarray


class QuantKVCache(NamedTuple):
    """int8 paged block pool (DESIGN.md §Quantized KV blocks): K/V rows are
    symmetric int8 over the head dim with f32 per-(block, kv-head,
    position) scales — (Dp + 4)/(2·Dp) of the bf16 bytes, ≈ 1.94×
    resident requests. A pytree like :class:`KVCache`, so the generic
    block gather/scatter/migration helpers work unchanged. Contiguous
    pieces (migration wire format) use the token-major twin: K/V
    ``[..., T, Hkv, Dh]``, scales ``[..., T, Hkv]``."""
    k: jnp.ndarray        # [NB, Hkv, BS, Dp] int8
    v: jnp.ndarray
    k_scale: jnp.ndarray  # [NB, Hkv, BS] f32
    v_scale: jnp.ndarray


def quantize_kv(x):
    """Symmetric int8 quantization over the last (head) axis:
    ``x ≈ int8 * scale`` with ``scale = amax/127`` per leading index.
    Returns ``(int8 values, f32 scales [x.shape[:-1]])``."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _pad_lanes(x, width: int):
    """Zero-pad the last (head) dim of ``x`` up to ``width`` lanes."""
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def scatter_pool(pool_l, blk, off, k, v):
    """Write new K/V rows into one layer's pool slice at physical
    ``(blk, h, off)`` for every kv head h — quantize-on-write when the
    pool is int8. ``blk``/``off`` are int32 of any matching shape S;
    ``k``/``v`` are [*S, Hkv, Dh] in compute dtype. Each update is one
    [Dp] row addressed by a full (block, head, position) index, so the
    scatter works on the pool's own row-major layout (a head-window
    scatter would make XLA relayout the pool around it)."""
    dp = pool_l.k.shape[-1]
    heads = jnp.arange(pool_l.k.shape[1], dtype=jnp.int32)
    idx = (blk[..., None], heads, off[..., None])        # each [*S, Hkv]
    if isinstance(pool_l, QuantKVCache):
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return QuantKVCache(pool_l.k.at[idx].set(_pad_lanes(kq, dp)),
                            pool_l.v.at[idx].set(_pad_lanes(vq, dp)),
                            pool_l.k_scale.at[idx].set(ks),
                            pool_l.v_scale.at[idx].set(vs))
    return KVCache(
        pool_l.k.at[idx].set(_pad_lanes(k, dp).astype(pool_l.k.dtype)),
        pool_l.v.at[idx].set(_pad_lanes(v, dp).astype(pool_l.v.dtype)))


def _pool_scales(pool_l):
    """(k_scale, v_scale) kernel operands — (None, None) for bf16 pools."""
    if isinstance(pool_l, QuantKVCache):
        return pool_l.k_scale, pool_l.v_scale
    return None, None


def _gather_dequant(pool_l, block_tables, head_dim: int):
    """Dense-path gather of a request-contiguous [B, NBT·BS, Hkv, Dh]
    view, dequantized to f32 when the pool is int8."""
    k_seq = paged_gather(pool_l.k, block_tables)[..., :head_dim]
    v_seq = paged_gather(pool_l.v, block_tables)[..., :head_dim]
    if isinstance(pool_l, QuantKVCache):
        ks = paged_gather(pool_l.k_scale, block_tables)   # [B, S, Hkv]
        vs = paged_gather(pool_l.v_scale, block_tables)
        k_seq = k_seq.astype(jnp.float32) * ks[..., None]
        v_seq = v_seq.astype(jnp.float32) * vs[..., None]
    return k_seq, v_seq


def blocks_to_piece(blocks, head_dim: int):
    """Gathered pool blocks (leaves [L, nb, Hkv, BS, Dp], int8 scales
    [L, nb, Hkv, BS]) -> the contiguous token-major piece of the
    migration wire format (leaves [L, 1, nb·BS, Hkv, Dh], scales
    [L, 1, nb·BS, Hkv]); the lane padding of rank-5 row leaves is
    dropped."""
    def one(a):
        a = jnp.swapaxes(a, 2, 3)
        a = a.reshape(a.shape[0], 1, -1, *a.shape[3:])
        return a[..., :head_dim] if a.ndim == 5 else a
    return jax.tree.map(one, blocks)


def piece_to_blocks(piece, nb: int, block_size: int, row_width: int):
    """Inverse of :func:`blocks_to_piece`: a contiguous piece (leaves
    [L, 1, T, Hkv, ...], T <= nb·BS) -> ``nb`` pool-layout blocks, the
    tail zero-filled and rank-5 row leaves lane-padded to ``row_width``."""
    def one(p):
        pad = [(0, 0)] * p.ndim
        pad[2] = (0, nb * block_size - p.shape[2])
        p = jnp.pad(p, pad)[:, 0]
        p = p.reshape(p.shape[0], nb, block_size, *p.shape[2:])
        p = jnp.swapaxes(p, 2, 3)
        return _pad_lanes(p, row_width) if p.ndim == 5 else p
    return jax.tree.map(one, piece)


def quantize_piece(piece):
    """Contiguous full-precision KV piece (:class:`KVCache`, leaves
    ``[..., Hkv, Dh]``) → its :class:`QuantKVCache` twin, for writing into
    an int8 pool. Zero-padding commutes: padded rows quantize to int8 0
    with scale 0, which dequantize back to exact zeros."""
    kq, ks = quantize_kv(piece.k)
    vq, vs = quantize_kv(piece.v)
    return QuantKVCache(kq, vq, ks, vs)


def dequantize_piece(piece, dtype):
    """:class:`QuantKVCache` piece → contiguous full-precision
    :class:`KVCache` in ``dtype``. Migration exports cross this, so the
    wire format stays the full-width layout and mixed bf16/int8 clusters
    interoperate (DESIGN.md §Migration wire format)."""
    return KVCache(
        (piece.k.astype(jnp.float32) * piece.k_scale[..., None]).astype(dtype),
        (piece.v.astype(jnp.float32) * piece.v_scale[..., None]).astype(dtype))


def _check_kv_backend(pool_l, attn_backend: str):
    if isinstance(pool_l, QuantKVCache) and attn_backend in ("grid", "flat"):
        raise ValueError(
            f"int8 KV pools need the 'fused' or 'dense' backend, "
            f"got {attn_backend!r}")


def attention_decode(p, cfg: ModelConfig, x, cache: KVCache, pos,
                     *, mrope_positions=None):
    """x [B, 1, D]; pos [B] int32 — number of tokens already in the cache.

    Writes the new token's K/V at ``pos`` (ring index ``pos % W`` when
    sliding) and attends over valid positions. Returns (out, new_cache).
    """
    B = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x)           # q [B,1,H,Dh]; k,v [B,1,Hkv,Dh]
    if cfg.use_mrope:
        mp = (mrope_positions if mrope_positions is not None
              else jnp.broadcast_to(pos[:, None, None], (B, 1, 3)))
        q = apply_mrope(q, mp, cfg.rope_theta)
        k = apply_mrope(k, mp, cfg.rope_theta)
    elif not cfg.learned_pos:
        pp = pos[:, None]
        q = apply_rope(q, pp, cfg.rope_theta)
        k = apply_rope(k, pp, cfg.rope_theta)

    S = cache.k.shape[1]
    W = cfg.sliding_window
    write_idx = (pos % W) if W else jnp.minimum(pos, S - 1)

    def write(buf, new):
        def one(b, n, i):
            return jax.lax.dynamic_update_slice(b, n, (i, 0, 0))
        out = jax.vmap(one)(buf, new, write_idx)
        if cfg.kv_cache_spec is not None:
            # pin the scatter result to the cache layout: GSPMD then
            # reshards the 1-token operand, not the multi-GiB cache
            out = jax.lax.with_sharding_constraint(out, cfg.kv_cache_spec)
        return out

    new_k = write(cache.k, k)
    new_v = write(cache.v, v)

    kpos = jnp.arange(S)[None, :]                               # [1, S]
    if W:
        # ring buffer: slot j holds absolute position p where p % W == j and
        # p <= pos; valid iff pos - W < p <= pos  <=> slot written recently.
        abs_pos = kpos + ((pos[:, None] - kpos) // W) * W        # latest write
        valid = (abs_pos >= 0) & (abs_pos >= pos[:, None] - W + 1) \
                & (abs_pos <= pos[:, None])
        mask = valid[:, None, None, None, :]
    else:
        mask = (kpos <= pos[:, None])[:, None, None, None, :]
    out = _gqa_sdpa(q, new_k, new_v, mask)
    return psum_if_tp(out.reshape(B, 1, -1) @ p["wo"], cfg), \
        KVCache(new_k, new_v)


# --------------------------------------------------------------------------
# Paged decode: one token vs. a global block pool + per-request block table.
# --------------------------------------------------------------------------
def paged_gather(pool, block_tables):
    """pool [NB, Hkv, BS, ...]; block_tables [B, NBT] int32 ->
    contiguous per-request view [B, NBT*BS, Hkv, ...]. Rows past a
    request's length come from padding table entries and must be masked
    by the caller."""
    B, NBT = block_tables.shape
    g = jnp.swapaxes(pool[block_tables], 2, 3)   # [B, NBT, BS, Hkv, ...]
    return g.reshape(B, NBT * pool.shape[2], *g.shape[3:])


def attention_decode_paged(p, cfg: ModelConfig, x, pool_l: KVCache,
                           block_tables, pos, *, mrope_positions=None,
                           attn_backend: str = "dense",
                           attn_interpret: bool = False,
                           attn_num_work: Optional[int] = None):
    """Block-table variant of :func:`attention_decode`.

    x [B, 1, D]; pool_l leaves [NB, Hkv, BS, Dp] — ONE layer's slice of the
    engine's global block pool; block_tables [B, NBT] int32 physical block
    ids (padded rows arbitrary); pos [B] int32 tokens already cached
    (``pos = -1`` marks a dead batch slot: its write lands in the padding
    row of its table and its attention length is 0).

    Writes the new token's K/V at physical ``(table[pos//BS], pos%BS)``
    and attends over the request's blocks only. Requests never share
    blocks, so the batched scatter has no duplicate indices. Full
    attention only — the sliding-window ring layout keeps the monolithic
    path (as do ssm/rwkv recurrent states).

    ``attn_backend`` (static — the serving engine bakes it in at jit
    time, see :func:`resolve_paged_backend`) picks how the attention
    itself runs. The kernel backends ("grid" / "flat") stream pool blocks
    HBM→VMEM by table indirection and never materialize the old
    ``[B, NBT·BS, Hkv, Dh]`` per-layer gather; "flat" additionally
    flattens the grid to ``attn_num_work`` (>= Σ_b ceil(L_b/BS)) work
    items so short requests stop paying the batch-max block count;
    "fused" runs the mixed kernel over the real items, counted on the
    device, and ignores ``attn_num_work``.
    """
    assert not cfg.sliding_window, "paged decode is full-attention only"
    B = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x)           # q [B,1,H,Dh]; k,v [B,1,Hkv,Dh]
    if cfg.use_mrope:
        mp = (mrope_positions if mrope_positions is not None
              else jnp.broadcast_to(pos[:, None, None], (B, 1, 3)))
        q = apply_mrope(q, mp, cfg.rope_theta)
        k = apply_mrope(k, mp, cfg.rope_theta)
    elif not cfg.learned_pos:
        pp = pos[:, None]
        q = apply_rope(q, pp, cfg.rope_theta)
        k = apply_rope(k, pp, cfg.rope_theta)

    _check_kv_backend(pool_l, attn_backend)
    BS = pool_l.k.shape[2]
    blk = jnp.take_along_axis(block_tables, (pos // BS)[:, None], axis=1)[:, 0]
    off = pos % BS
    new_pool = scatter_pool(pool_l, blk, off, k[:, 0], v[:, 0])

    if attn_backend == "fused":
        # one-launch mixed kernel degenerates to all-decode tags at C = 1;
        # ctx = pos, seg = 1 (dead slots: total = 0 -> zero work items)
        from repro.kernels.mixed_attention import paged_mixed_attention
        ks, vs = _pool_scales(new_pool)
        o = paged_mixed_attention(
            q, new_pool.k, new_pool.v, block_tables, pos,
            jnp.ones_like(pos), jnp.zeros_like(pos), ks, vs,
            interpret=attn_interpret)
        out = o.astype(q.dtype)                  # [B, 1, H, Dh]
    elif attn_backend != "dense":
        # Pallas path: the pool stays put; the kernel chases the block
        # table. lengths = pos + 1 (dead slots: 0 -> zero work items).
        from repro.kernels.decode_attention import (
            paged_decode_attention, paged_decode_attention_flat)
        lengths = pos + 1
        if attn_backend == "flat":
            o = paged_decode_attention_flat(
                q[:, 0], new_pool.k, new_pool.v, block_tables, lengths,
                num_work=attn_num_work, interpret=attn_interpret)
        else:
            o = paged_decode_attention(
                q[:, 0], new_pool.k, new_pool.v, block_tables, lengths,
                interpret=attn_interpret)
        out = o[:, None].astype(q.dtype)         # [B, 1, H, Dh]
    else:
        k_seq, v_seq = _gather_dequant(new_pool, block_tables, cfg.head_dim)
        kpos = jnp.arange(k_seq.shape[1])[None, :]
        mask = (kpos <= pos[:, None])[:, None, None, None, :]
        out = _gqa_sdpa(q, k_seq, v_seq, mask)
    return psum_if_tp(out.reshape(B, 1, -1) @ p["wo"], cfg), new_pool


def attention_prefill_chunk_paged(p, cfg: ModelConfig, x, pool_l: KVCache,
                                  block_tables, ctx_len, chunk_len,
                                  *, mrope_positions=None,
                                  attn_backend: str = "dense",
                                  attn_interpret: bool = False):
    """Chunked prefill against the paged pool (DESIGN.md §Chunked prefill).

    x [B, C, D] — B prompt chunks of C tokens (rows past ``chunk_len``
    are padding); pool_l leaves [NB, Hkv, BS, Dp] — ONE layer's slice of
    the global block pool; block_tables [B, NBT] int32 covering at least
    ``ceil((ctx_len + C)/BS)`` rows (the tail padded with a garbage
    block, so padding-row writes never touch live data); ctx_len [B] (or
    scalar) int32 tokens already written for each chunk's request;
    chunk_len [B] (or scalar) int32 real tokens in each chunk.

    Writes the chunk's K/V into the pool at logical positions
    ``ctx..ctx+C-1`` (RoPE applied at the true global positions), then
    attends each query causally over its own chunk **plus the previously
    written context**, read through the block table — so a partial prompt
    lives in the same pool as decode state and later chunks/decodes see
    exactly the rows earlier chunks wrote. Returns (out [B, C, D],
    new pool); output rows past ``chunk_len`` are garbage (the caller
    keeps only the last real position's logits).
    """
    assert not cfg.sliding_window, "paged prefill is full-attention only"
    B, C, _ = x.shape
    ctx = jnp.broadcast_to(jnp.asarray(ctx_len, jnp.int32).reshape(-1), (B,))
    clen = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32).reshape(-1),
                            (B,))
    positions = ctx[:, None] + jnp.arange(C, dtype=jnp.int32)[None]  # [B, C]
    q, k, v = _project_qkv(p, cfg, x)
    if cfg.use_mrope:
        mp = (mrope_positions if mrope_positions is not None
              else jnp.broadcast_to(positions[..., None], (B, C, 3)))
        q = apply_mrope(q, mp, cfg.rope_theta)
        k = apply_mrope(k, mp, cfg.rope_theta)
    elif not cfg.learned_pos:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    _check_kv_backend(pool_l, attn_backend)
    BS = pool_l.k.shape[2]
    blk = jnp.take_along_axis(block_tables, positions // BS, axis=1)  # [B, C]
    off = positions % BS
    # chunk positions are distinct per request and requests never share
    # blocks, so the batched scatter has no duplicate (blk, off) pairs
    new_pool = scatter_pool(pool_l, blk, off, k, v)

    if attn_backend == "fused":
        # one-launch mixed kernel with all-chunk tags
        from repro.kernels.mixed_attention import paged_mixed_attention
        ks, vs = _pool_scales(new_pool)
        out = paged_mixed_attention(
            q, new_pool.k, new_pool.v, block_tables, ctx, clen,
            jnp.ones_like(ctx), ks, vs, interpret=attn_interpret)
        out = out.astype(q.dtype)
    elif attn_backend != "dense":
        # Pallas path: the pool stays in HBM; the flat work-list kernel
        # chases the block table (cost ∝ chunk × context blocks)
        from repro.kernels.prefill_attention import paged_prefill_attention
        out = paged_prefill_attention(q, new_pool.k, new_pool.v,
                                      block_tables, ctx, clen,
                                      interpret=attn_interpret)
        out = out.astype(q.dtype)
    else:
        k_seq, v_seq = _gather_dequant(new_pool, block_tables, cfg.head_dim)
        kpos = jnp.arange(k_seq.shape[1])[None, None, :]        # [1, 1, S]
        mask = (kpos <= positions[:, :, None])[:, None, None]   # [B,1,1,C,S]
        out = _gqa_sdpa(q, k_seq, v_seq, mask)
    return psum_if_tp(out.reshape(B, C, -1) @ p["wo"], cfg), new_pool


def attention_mixed_paged(p, cfg: ModelConfig, x_dec, x_ck, pool_l,
                          bt_dec, bt_ck, pos, ctx_len, chunk_len, *,
                          attn_backend: str = "fused",
                          attn_interpret: bool = False):
    """ONE fused attention launch for a whole mixed iteration: the decode
    batch advances one token while prompt chunks prefill beside it
    (DESIGN.md §Fused mixed-iteration attention).

    x_dec [Bd, 1, D] — the decode batch (``pos = -1`` marks dead slots);
    x_ck  [Bp, C, D] — the prefill chunks (rows past ``chunk_len`` are
    padding); pool_l — ONE layer's pool slice (:class:`KVCache` or
    :class:`QuantKVCache`); bt_dec [Bd, NBT] / bt_ck [Bp, NBT'] block
    tables (padded to a common width here); pos [Bd] tokens already
    cached per decode slot; ctx_len/chunk_len [Bp] as in
    :func:`attention_prefill_chunk_paged`.

    Projection/RoPE/wo stay per-half — padding decode tokens through the
    chunk width would inflate the MXU work C× — and only the attention
    itself runs as one tagged work list: decode segments (tag 0,
    ctx = pos, seg = 1) interleaved with chunk segments (tag 1). Returns
    ``(out_dec [Bd, 1, D], out_ck [Bp, C, D], new_pool)``.
    """
    assert not cfg.sliding_window, "paged mixed step is full-attention only"
    assert not cfg.use_mrope, "paged mixed step: RoPE / learned-pos only"
    _check_kv_backend(pool_l, attn_backend)
    Bd = x_dec.shape[0]
    Bp, C, _ = x_ck.shape
    ctx = jnp.broadcast_to(jnp.asarray(ctx_len, jnp.int32).reshape(-1), (Bp,))
    clen = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32).reshape(-1),
                            (Bp,))
    positions = ctx[:, None] + jnp.arange(C, dtype=jnp.int32)[None]  # [Bp, C]

    qd, kd, vd = _project_qkv(p, cfg, x_dec)
    qc, kc, vc = _project_qkv(p, cfg, x_ck)
    if not cfg.learned_pos:
        qd = apply_rope(qd, pos[:, None], cfg.rope_theta)
        kd = apply_rope(kd, pos[:, None], cfg.rope_theta)
        qc = apply_rope(qc, positions, cfg.rope_theta)
        kc = apply_rope(kc, positions, cfg.rope_theta)

    BS = pool_l.k.shape[2]
    blk_d = jnp.take_along_axis(bt_dec, (pos // BS)[:, None], axis=1)[:, 0]
    blk_c = jnp.take_along_axis(bt_ck, positions // BS, axis=1)
    pool1 = scatter_pool(pool_l, blk_d, pos % BS, kd[:, 0], vd[:, 0])
    new_pool = scatter_pool(pool1, blk_c, positions % BS, kc, vc)

    ctx_all = jnp.concatenate([pos, ctx])
    slen_all = jnp.concatenate([jnp.ones_like(pos), clen])

    if attn_backend == "fused":
        from repro.kernels.mixed_attention import paged_mixed_attention
        # decode q rides in row 0 of a chunk-wide tile; block tables pad
        # to a common width (padded entries are only reached clamped, on
        # work items the total guard skips)
        NBT = max(bt_dec.shape[1], bt_ck.shape[1])
        bt_all = jnp.concatenate([
            jnp.pad(bt_dec, ((0, 0), (0, NBT - bt_dec.shape[1]))),
            jnp.pad(bt_ck, ((0, 0), (0, NBT - bt_ck.shape[1])))])
        q_all = jnp.concatenate([
            jnp.pad(qd, ((0, 0), (0, C - 1), (0, 0), (0, 0))), qc])
        tags = jnp.concatenate([jnp.zeros_like(pos), jnp.ones_like(ctx)])
        ks, vs = _pool_scales(new_pool)
        o = paged_mixed_attention(
            q_all, new_pool.k, new_pool.v, bt_all, ctx_all, slen_all, tags,
            ks, vs, interpret=attn_interpret)
        o = o.astype(qd.dtype)
        out_d, out_c = o[:Bd, :1], o[Bd:]
    else:
        # dense bit-parity reference: the same two-gather SDPA halves the
        # separate-kernel path runs (CPU/debug fallback)
        kd_seq, vd_seq = _gather_dequant(new_pool, bt_dec, cfg.head_dim)
        kpos = jnp.arange(kd_seq.shape[1])[None, :]
        mask = (kpos <= pos[:, None])[:, None, None, None, :]
        out_d = _gqa_sdpa(qd, kd_seq, vd_seq, mask)
        kc_seq, vc_seq = _gather_dequant(new_pool, bt_ck, cfg.head_dim)
        kpos = jnp.arange(kc_seq.shape[1])[None, None, :]
        mask = (kpos <= positions[:, :, None])[:, None, None]
        out_c = _gqa_sdpa(qc, kc_seq, vc_seq, mask)
    return (psum_if_tp(out_d.reshape(Bd, 1, -1) @ p["wo"], cfg),
            psum_if_tp(out_c.reshape(Bp, C, -1) @ p["wo"], cfg), new_pool)


def make_paged_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
                    dtype=None, kv_dtype: str = "bf16", layers=None):
    """Zeroed global block pool: leaves [NB, Hkv, BS, Dp] (head-major,
    rows lane-padded to ``pool_row_width(head_dim)``; DESIGN.md §Block
    pool layout), with a leading [L] axis when ``layers`` is given.
    ``kv_dtype="int8"`` returns the quantized layout (zero scales
    [.., NB, Hkv, BS], so garbage blocks dequantize to exact zeros)."""
    assert kv_dtype in KV_DTYPES, kv_dtype
    shape = (num_blocks, cfg.num_kv_heads, block_size,
             pool_row_width(cfg.head_dim))
    if layers is not None:
        shape = (layers,) + shape
    if kv_dtype == "int8":
        sshape = shape[:-1]
        return QuantKVCache(jnp.zeros(shape, jnp.int8),
                            jnp.zeros(shape, jnp.int8),
                            jnp.zeros(sshape, jnp.float32),
                            jnp.zeros(sshape, jnp.float32))
    dt = dtype or cfg.dtype
    return KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt))


# --------------------------------------------------------------------------
# Cross-attention (whisper decoder): KV precomputed from encoder output.
# --------------------------------------------------------------------------
def cross_attention(p, cfg: ModelConfig, x, enc_kv: KVCache):
    B, T, _ = x.shape
    h, dh = cfg.num_heads, cfg.head_dim
    q = (x @ p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, T, h, dh)
    out = _gqa_sdpa(q, enc_kv.k, enc_kv.v, None)
    return out.reshape(B, T, -1) @ p["wo"]


def encode_cross_kv(p, cfg: ModelConfig, enc_out):
    B, S, _ = enc_out.shape
    hk, dh = cfg.num_kv_heads, cfg.head_dim
    k = enc_out @ p["wk"]
    v = enc_out @ p["wv"]
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    return KVCache(k.reshape(B, S, hk, dh), v.reshape(B, S, hk, dh))


def make_cache(cfg: ModelConfig, batch: int, seq: int, dtype=None) -> KVCache:
    """Preallocate a zeroed cache (ring of size window when sliding)."""
    S = min(seq, cfg.sliding_window) if cfg.sliding_window else seq
    dt = dtype or cfg.dtype
    shape = (batch, S, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
