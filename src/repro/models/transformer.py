"""Decoder-only transformer stack (dense GQA / MoE / VLM flavors).

Per-layer weights are stacked on a leading ``L`` axis and the layer loop is
``jax.lax.scan`` — fast compiles at 48+ layers and remat-friendly. The same
block code serves train (full-sequence), prefill (returns KV cache) and
decode (one token against the cache).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.attention import KVCache
from repro.models.common import (ModelConfig, embed_init, rms_norm,
                                 dense_init, maybe_shard_activations)
from repro.models.mlp import ffn, init_ffn, init_moe, moe


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------
def init_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    p = {
        "ln_attn": jnp.ones((cfg.d_model,), cfg.dtype),
        "ln_mlp": jnp.ones((cfg.d_model,), cfg.dtype),
        "attn": attn.init_attention(ks[0], cfg),
    }
    if cfg.num_experts:
        p["moe"] = init_moe(ks[1], cfg)
        if cfg.dense_residual:  # arctic: parallel dense FFN
            p["ffn"] = init_ffn(ks[2], cfg)
            p["ln_res"] = jnp.ones((cfg.d_model,), cfg.dtype)
    else:
        p["ffn"] = init_ffn(ks[1], cfg)
    return p


def init_decoder(key, cfg: ModelConfig):
    ks = jax.random.split(key, cfg.num_layers + 3)
    layers = [init_block(ks[i], cfg) for i in range(cfg.num_layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    p = {
        "embed": embed_init(ks[-3], (cfg.vocab_size, cfg.d_model), cfg.dtype),
        "layers": stacked,
        "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[-2], (cfg.d_model, cfg.vocab_size), cfg.dtype)
    return p


# --------------------------------------------------------------------------
# Block forward (shared by all modes)
# --------------------------------------------------------------------------
def _mlp_part(pl, cfg: ModelConfig, x):
    """Returns (mlp_out, aux)."""
    h = rms_norm(x, pl["ln_mlp"], cfg.norm_eps)
    if cfg.num_experts:
        out, aux = moe(pl["moe"], cfg, h, cfg.moe_impl)
        if cfg.dense_residual:
            out = out + ffn(pl["ffn"], cfg, rms_norm(x, pl["ln_res"], cfg.norm_eps))
        return out, aux
    return ffn(pl["ffn"], cfg, h), jnp.float32(0.0)


def block_full(pl, cfg: ModelConfig, x, positions, mrope_positions=None):
    """Full-sequence pass (train / prefill). Returns (x, cache_l, aux)."""
    h = rms_norm(x, pl["ln_attn"], cfg.norm_eps)
    a, (k, v) = attn.attention_prefill(pl["attn"], cfg, h, positions,
                                       mrope_positions=mrope_positions)
    x = x + a
    m, aux = _mlp_part(pl, cfg, x)
    return x + m, KVCache(k, v), aux


def block_decode(pl, cfg: ModelConfig, x, cache_l: KVCache, pos,
                 mrope_positions=None):
    h = rms_norm(x, pl["ln_attn"], cfg.norm_eps)
    a, new_cache = attn.attention_decode(pl["attn"], cfg, h, cache_l, pos,
                                         mrope_positions=mrope_positions)
    x = x + a
    m, aux = _mlp_part(pl, cfg, x)
    return x + m, new_cache, aux


# --------------------------------------------------------------------------
# Embedding in/out
# --------------------------------------------------------------------------
def embed_tokens(p, cfg: ModelConfig, tokens, vision_embeds=None,
                 vision_mask=None):
    if cfg.tp_axis is not None:
        # vocab-sharded lookup (DESIGN.md §Sharded serving): each shard
        # holds V/TP contiguous embedding rows; out-of-range ids read a
        # clamped row, are zeroed, and the psum assembles the one real
        # row — exact, because exactly one shard contributes non-zeros.
        vloc = p["embed"].shape[0]
        idx = jax.lax.axis_index(cfg.tp_axis)
        local = tokens - idx * vloc
        ok = (local >= 0) & (local < vloc)
        x = jnp.where(ok[..., None],
                      p["embed"][jnp.clip(local, 0, vloc - 1)], 0)
        x = jax.lax.psum(x, cfg.tp_axis)
    else:
        x = p["embed"][tokens]
    if vision_embeds is not None and vision_mask is not None:
        # place the precomputed patch embeddings (VLM stub frontend) at the
        # masked positions, in order.
        B, T, D = x.shape
        idx = jnp.cumsum(vision_mask.astype(jnp.int32), axis=1) - 1
        idx = jnp.clip(idx, 0, vision_embeds.shape[1] - 1)
        gathered = jnp.take_along_axis(vision_embeds, idx[..., None], axis=1)
        x = jnp.where(vision_mask[..., None], gathered.astype(x.dtype), x)
    return x


def unembed(p, cfg: ModelConfig, x):
    w = p["embed"].T if cfg.tie_embeddings else p["unembed"]
    logits = x @ w
    if cfg.tp_axis is not None:
        # each shard computed V/TP logit columns (tied embeddings shard V
        # on dim 0, so the transpose lines up); the all-gather makes the
        # full vocab visible on every shard — argmax sampling then runs
        # replicated INSIDE the jitted step, keeping the one-d2h-per-step
        # discipline (DESIGN.md §Sharded serving).
        logits = jax.lax.all_gather(logits, cfg.tp_axis,
                                    axis=logits.ndim - 1, tiled=True)
    return logits


# --------------------------------------------------------------------------
# Full-stack passes
# --------------------------------------------------------------------------
def forward_full(p, cfg: ModelConfig, tokens, *, vision_embeds=None,
                 vision_mask=None, mrope_positions=None, return_cache=False,
                 remat: bool = False, last_only: bool = False,
                 last_index=None):
    """Train / prefill pass. Returns (logits, cache|None, aux).

    ``last_index`` (traced scalar) unembeds ONLY position ``last_index``
    — the bucketed-prefill path, where the prompt is padded to a pow2
    length and the true last token sits mid-sequence. Causality makes the
    K/V rows and logits at positions < true length independent of the
    padding tail."""
    x = embed_tokens(p, cfg, tokens, vision_embeds, vision_mask)
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    if cfg.use_mrope and mrope_positions is None:
        mrope_positions = jnp.broadcast_to(positions[..., None], (B, T, 3))

    def body(carry, pl):
        x, aux = carry
        x = maybe_shard_activations(x, cfg)
        x, cache_l, a = block_full(pl, cfg, x, positions, mrope_positions)
        return (x, aux + a), cache_l if return_cache else 0

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux), caches = jax.lax.scan(body_fn, (x, jnp.float32(0.0)), p["layers"])
    x = rms_norm(x, p["ln_f"], cfg.norm_eps)
    if last_index is not None:    # bucketed prefill: true last position
        x = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    elif last_only:   # serving prefill needs next-token logits only
        x = x[:, -1:]
    logits = unembed(p, cfg, x)
    return logits, (caches if return_cache else None), aux


def forward_decode(p, cfg: ModelConfig, token, cache: KVCache, pos,
                   *, mrope_positions=None):
    """token [B] int32; cache leaves [L, B, S, Hkv, Dh]; pos [B] int32.
    Returns (logits [B, V], new_cache)."""
    x = embed_tokens(p, cfg, token[:, None])
    if cfg.use_mrope and mrope_positions is None:
        B = token.shape[0]
        mrope_positions = jnp.broadcast_to(pos[:, None, None], (B, 1, 3))

    def body(x, layer):
        pl, cache_l = layer
        x, new_cache_l, _ = block_decode(pl, cfg, x, cache_l, pos,
                                         mrope_positions)
        return x, new_cache_l

    x, new_cache = jax.lax.scan(body, x, (p["layers"], cache))
    x = rms_norm(x, p["ln_f"], cfg.norm_eps)
    return unembed(p, cfg, x)[:, 0], new_cache


def block_decode_paged(pl, cfg: ModelConfig, x, pool_l: KVCache,
                       block_tables, pos, mrope_positions=None,
                       attn_backend: str = "dense",
                       attn_interpret: bool = False,
                       attn_num_work=None):
    h = rms_norm(x, pl["ln_attn"], cfg.norm_eps)
    a, new_pool = attn.attention_decode_paged(pl["attn"], cfg, h, pool_l,
                                              block_tables, pos,
                                              mrope_positions=mrope_positions,
                                              attn_backend=attn_backend,
                                              attn_interpret=attn_interpret,
                                              attn_num_work=attn_num_work)
    x = x + a
    m, aux = _mlp_part(pl, cfg, x)
    return x + m, new_pool, aux


def forward_decode_paged(p, cfg: ModelConfig, token, pool: KVCache,
                         block_tables, pos, *, mrope_positions=None,
                         attn_backend: str = "dense",
                         attn_interpret: bool = False,
                         attn_num_work=None):
    """token [B] int32; pool leaves [L, NB, Hkv, BS, Dp] (global block
    pool); block_tables [B, NBT] int32; pos [B] int32 (-1 = dead slot).
    Returns (logits [B, V], new_pool). The attn_* knobs are static
    backend selectors (DESIGN.md §Decode hot path), baked in by the
    engine via functools.partial before jit."""
    x = embed_tokens(p, cfg, token[:, None])
    if cfg.use_mrope and mrope_positions is None:
        B = token.shape[0]
        mrope_positions = jnp.broadcast_to(pos[:, None, None], (B, 1, 3))

    def body(x, layer):
        pl, pool_l = layer
        x, new_pool_l, _ = block_decode_paged(pl, cfg, x, pool_l,
                                              block_tables, pos,
                                              mrope_positions,
                                              attn_backend=attn_backend,
                                              attn_interpret=attn_interpret,
                                              attn_num_work=attn_num_work)
        return x, new_pool_l

    x, new_pool = jax.lax.scan(body, x, (p["layers"], pool))
    x = rms_norm(x, p["ln_f"], cfg.norm_eps)
    return unembed(p, cfg, x)[:, 0], new_pool


def block_prefill_chunk(pl, cfg: ModelConfig, x, pool_l: KVCache,
                        block_tables, ctx_len, chunk_len,
                        mrope_positions=None, attn_backend: str = "dense",
                        attn_interpret: bool = False):
    h = rms_norm(x, pl["ln_attn"], cfg.norm_eps)
    a, new_pool = attn.attention_prefill_chunk_paged(
        pl["attn"], cfg, h, pool_l, block_tables, ctx_len, chunk_len,
        mrope_positions=mrope_positions, attn_backend=attn_backend,
        attn_interpret=attn_interpret)
    x = x + a
    m, aux = _mlp_part(pl, cfg, x)
    return x + m, new_pool, aux


def forward_prefill_chunk(p, cfg: ModelConfig, tokens, pool: KVCache,
                          block_tables, ctx_len, chunk_len, *,
                          mrope_positions=None, attn_backend: str = "dense",
                          attn_interpret: bool = False):
    """One prompt *chunk* through the stack against the paged pool
    (DESIGN.md §Chunked prefill): tokens [B, C] int32 (rows past
    ``chunk_len`` are padding), pool leaves [L, NB, Hkv, BS, Dp],
    block_tables [B, NBT], ctx_len / chunk_len traced int32 scalars (or
    [B]). Every layer writes the chunk's K/V into its pool slice and
    attends over the written context + chunk, so calling this
    chunk-by-chunk reproduces the whole-prompt prefill's cache rows and
    next-token logits exactly. Returns (last-real-token logits [B, V],
    new pool)."""
    x = embed_tokens(p, cfg, tokens)
    B, C = tokens.shape
    ctx = jnp.broadcast_to(jnp.asarray(ctx_len, jnp.int32).reshape(-1), (B,))
    if cfg.use_mrope and mrope_positions is None:
        positions = ctx[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        mrope_positions = jnp.broadcast_to(positions[..., None], (B, C, 3))

    def body(x, layer):
        pl_, pool_l = layer
        x, new_pool_l, _ = block_prefill_chunk(
            pl_, cfg, x, pool_l, block_tables, ctx_len, chunk_len,
            mrope_positions, attn_backend=attn_backend,
            attn_interpret=attn_interpret)
        return x, new_pool_l

    x, new_pool = jax.lax.scan(body, x, (p["layers"], pool))
    x = rms_norm(x, p["ln_f"], cfg.norm_eps)
    # each chunk's last REAL position — on the prompt's final chunk this
    # is the request's first-token distribution
    clen = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32).reshape(-1),
                            (B,))
    x = jnp.take_along_axis(x, (clen - 1)[:, None, None], axis=1)
    return unembed(p, cfg, x)[:, 0], new_pool


def block_mixed(pl, cfg: ModelConfig, x_dec, x_ck, pool_l, bt_dec, bt_ck,
                pos, ctx_len, chunk_len, attn_backend: str = "fused",
                attn_interpret: bool = False):
    hd = rms_norm(x_dec, pl["ln_attn"], cfg.norm_eps)
    hc = rms_norm(x_ck, pl["ln_attn"], cfg.norm_eps)
    ad, ac, new_pool = attn.attention_mixed_paged(
        pl["attn"], cfg, hd, hc, pool_l, bt_dec, bt_ck, pos, ctx_len,
        chunk_len, attn_backend=attn_backend, attn_interpret=attn_interpret)
    x_dec = x_dec + ad
    x_ck = x_ck + ac
    md, aux_d = _mlp_part(pl, cfg, x_dec)
    mc, aux_c = _mlp_part(pl, cfg, x_ck)
    return x_dec + md, x_ck + mc, new_pool, aux_d + aux_c


def forward_mixed(p, cfg: ModelConfig, dec_token, ck_tokens, pool,
                  bt_dec, bt_ck, pos, ctx_len, chunk_len, *,
                  attn_backend: str = "fused", attn_interpret: bool = False):
    """One whole MIXED iteration through the stack: the decode batch
    (``dec_token [Bd]``, ``pos [Bd]``, -1 = dead slot) advances one token
    while prompt chunks (``ck_tokens [Bp, C]``, ``ctx_len``/``chunk_len``)
    prefill beside it — each layer runs ONE fused attention launch over
    the tagged decode+chunk work list (DESIGN.md §Fused mixed-iteration
    attention). Activations stay per-half through embed/QKV/MLP so decode
    tokens never pay the chunk width C in linear work. Returns
    ``(dec_logits [Bd, V], ck_logits [Bp, V], new_pool)`` — ck_logits at
    each chunk's last real position, as in :func:`forward_prefill_chunk`.
    """
    x_dec = embed_tokens(p, cfg, dec_token[:, None])
    x_ck = embed_tokens(p, cfg, ck_tokens)
    Bp, C = ck_tokens.shape

    def body(carry, layer):
        x_dec, x_ck = carry
        pl_, pool_l = layer
        x_dec, x_ck, new_pool_l, _ = block_mixed(
            pl_, cfg, x_dec, x_ck, pool_l, bt_dec, bt_ck, pos, ctx_len,
            chunk_len, attn_backend=attn_backend,
            attn_interpret=attn_interpret)
        return (x_dec, x_ck), new_pool_l

    (x_dec, x_ck), new_pool = jax.lax.scan(body, (x_dec, x_ck),
                                           (p["layers"], pool))
    x_dec = rms_norm(x_dec, p["ln_f"], cfg.norm_eps)
    x_ck = rms_norm(x_ck, p["ln_f"], cfg.norm_eps)
    clen = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32).reshape(-1),
                            (Bp,))
    x_ck = jnp.take_along_axis(x_ck, (clen - 1)[:, None, None], axis=1)
    return (unembed(p, cfg, x_dec)[:, 0], unembed(p, cfg, x_ck)[:, 0],
            new_pool)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=None, kv_dtype: str = "bf16"):
    """Global paged KV pool: leaves [L, NB, Hkv, BS, Dp] (DESIGN.md §Block
    pool layout) — int8 rows + f32 [L, NB, Hkv, BS] scales when
    ``kv_dtype="int8"`` (§Quantized KV blocks). Blocks are owned by
    requests via the engine's BlockAllocator; the model never sees
    ownership, only block tables."""
    assert not cfg.sliding_window, "paged cache is full-attention only"
    return attn.make_paged_pool(cfg, num_blocks, block_size, dtype=dtype,
                                kv_dtype=kv_dtype, layers=cfg.num_layers)


def init_cache(cfg: ModelConfig, batch: int, seq: int, dtype=None) -> KVCache:
    S = min(seq, cfg.sliding_window) if cfg.sliding_window else seq
    dt = dtype or cfg.dtype
    shape = (cfg.num_layers, batch, S, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
