"""Unified Model API over the zoo.

Every family exposes the same four entry points, so the trainer, the
serving engine, and the multi-pod dry-run treat architectures uniformly:

    model = build_model(cfg)
    params = model.init(rng)
    loss, aux = model.loss(params, batch)                  # train_4k
    logits, cache = model.prefill(params, batch, cache_len)  # prefill_32k
    logits, cache = model.decode_step(params, cache, token, pos)  # decode_*

``batch`` is a dict; family-specific extras (audio/vision stub embeddings,
M-RoPE position ids) ride along in it. Caches are opaque pytrees.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import mamba2, rwkv6, transformer, whisper
from repro.models.attention import KVCache
from repro.models.common import ModelConfig, softmax_xent


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Any]
    loss: Callable[..., Any]            # (params, batch) -> (loss, aux)
    prefill: Callable[..., Any]         # (params, batch, cache_len) -> (logits, cache)
    decode_step: Callable[..., Any]     # (params, cache, token, pos) -> (logits, cache)
    init_cache: Callable[..., Any]      # (batch_size, seq) -> cache
    # Paged (block-table) serving path — decoder-only full-attention
    # families; None elsewhere (ssm/rwkv recurrent state and sliding-window
    # ring buffers keep the monolithic layout).
    #   init_paged_cache(num_blocks, block_size) -> pool [L, NB, Hkv, BS, Dp]
    #       (head-major blocks, rows lane-padded to Dp = head_dim rounded
    #       up to 128; int8 pools add f32 scales [L, NB, Hkv, BS])
    #   decode_step_paged(params, pool, token, block_tables, pos)
    #       -> (logits, pool)
    init_paged_cache: Optional[Callable[..., Any]] = None
    decode_step_paged: Optional[Callable[..., Any]] = None
    # Bucketed prefill (device-resident engines): tokens padded to a pow2
    # bucket, true_len a traced scalar — one compile per bucket instead of
    # one per distinct prompt length.
    #   prefill_bucketed(params, batch, true_len)
    #       -> (last-token logits [B, V], prompt-cache piece [L, B, P, ...])
    prefill_bucketed: Optional[Callable[..., Any]] = None
    # Chunked paged prefill (DESIGN.md §Chunked prefill): one prompt chunk
    # written + attended against the paged pool, so the engine can pack
    # prompt chunks into decode iterations instead of freezing the batch
    # for a whole long prompt.
    #   prefill_chunk(params, pool, tokens, block_tables, ctx_len,
    #                 chunk_len, *, attn_backend, attn_interpret)
    #       -> (last-real-token logits [B, V], new pool)
    prefill_chunk: Optional[Callable[..., Any]] = None
    # Fused mixed iteration (DESIGN.md §Fused mixed-iteration attention):
    # the decode batch and the prefill chunks of one engine step through
    # the stack with ONE attention launch per layer.
    #   mixed_step(params, pool, dec_token, ck_tokens, bt_dec, bt_ck, pos,
    #              ctx_len, chunk_len, *, attn_backend, attn_interpret)
    #       -> (dec_logits [Bd, V], ck_logits [Bp, V], new pool)
    mixed_step: Optional[Callable[..., Any]] = None

    @property
    def supports_paged(self) -> bool:
        return self.decode_step_paged is not None


def _relay_kv(cache_pref: KVCache, cfg: ModelConfig, cache_len: int) -> KVCache:
    """Prompt-length per-layer KV [L,B,T,H,D] -> preallocated decode buffer
    [L,B,W,H,D] with ring layout (slot = abs position % W when sliding)."""
    L, B, T = cache_pref.k.shape[:3]
    W = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    take = min(T, W)
    idx = jnp.arange(T - take, T) % W

    def relay(k):
        buf = jnp.zeros((L, B, W) + k.shape[3:], k.dtype)
        return buf.at[:, :, idx].set(k[:, :, T - take:])

    return KVCache(relay(cache_pref.k), relay(cache_pref.v))


# --------------------------------------------------------------------------
# Decoder-only family (dense / MoE / VLM)
# --------------------------------------------------------------------------
def _decoder_model(cfg: ModelConfig) -> Model:
    def init(rng):
        return transformer.init_decoder(rng, cfg)

    def loss(params, batch, remat: bool = False):
        tokens = batch["tokens"]
        logits, _, aux = transformer.forward_full(
            params, cfg, tokens,
            vision_embeds=batch.get("vision_embeds"),
            vision_mask=batch.get("vision_mask"),
            mrope_positions=batch.get("mrope_positions"),
            remat=remat)
        mask = batch.get("loss_mask")
        xe = softmax_xent(logits[:, :-1], tokens[:, 1:],
                          None if mask is None else mask[:, 1:])
        return xe + aux, {"xent": xe, "aux": aux}

    def prefill(params, batch, cache_len: Optional[int] = None):
        tokens = batch["tokens"]
        logits, caches, _ = transformer.forward_full(
            params, cfg, tokens,
            vision_embeds=batch.get("vision_embeds"),
            vision_mask=batch.get("vision_mask"),
            mrope_positions=batch.get("mrope_positions"),
            return_cache=True, last_only=True)
        cache = _relay_kv(caches, cfg, cache_len or tokens.shape[1])
        return logits[:, -1], cache

    def decode_step(params, cache, token, pos, **extras):
        return transformer.forward_decode(params, cfg, token, cache, pos,
                                          **extras)

    def init_cache(batch_size: int, seq: int):
        return transformer.init_cache(cfg, batch_size, seq)

    if cfg.sliding_window:
        # ring-buffer cache layout is incompatible with block tables;
        # such configs serve through the monolithic fallback
        return Model(cfg, init, loss, prefill, decode_step, init_cache)

    def decode_step_paged(params, pool, token, block_tables, pos, **extras):
        return transformer.forward_decode_paged(params, cfg, token, pool,
                                                block_tables, pos, **extras)

    def init_paged_cache(num_blocks: int, block_size: int,
                         kv_dtype: str = "bf16"):
        return transformer.init_paged_cache(cfg, num_blocks, block_size,
                                            kv_dtype=kv_dtype)

    def prefill_bucketed(params, batch, true_len):
        tokens = batch["tokens"]
        logits, caches, _ = transformer.forward_full(
            params, cfg, tokens,
            vision_embeds=batch.get("vision_embeds"),
            vision_mask=batch.get("vision_mask"),
            mrope_positions=batch.get("mrope_positions"),
            return_cache=True, last_index=true_len - 1)
        return logits[:, 0], caches

    def prefill_chunk(params, pool, tokens, block_tables, ctx_len,
                      chunk_len, *, attn_backend: str = "dense",
                      attn_interpret: bool = False):
        return transformer.forward_prefill_chunk(
            params, cfg, tokens, pool, block_tables, ctx_len, chunk_len,
            attn_backend=attn_backend, attn_interpret=attn_interpret)

    def mixed_step(params, pool, dec_token, ck_tokens, bt_dec, bt_ck, pos,
                   ctx_len, chunk_len, *, attn_backend: str = "fused",
                   attn_interpret: bool = False):
        return transformer.forward_mixed(
            params, cfg, dec_token, ck_tokens, pool, bt_dec, bt_ck, pos,
            ctx_len, chunk_len, attn_backend=attn_backend,
            attn_interpret=attn_interpret)

    return Model(cfg, init, loss, prefill, decode_step, init_cache,
                 init_paged_cache=init_paged_cache,
                 decode_step_paged=decode_step_paged,
                 prefill_bucketed=prefill_bucketed,
                 prefill_chunk=prefill_chunk,
                 mixed_step=mixed_step)


# --------------------------------------------------------------------------
# RWKV6
# --------------------------------------------------------------------------
def _rwkv_model(cfg: ModelConfig) -> Model:
    def init(rng):
        return rwkv6.init_model(rng, cfg)

    def loss(params, batch, remat: bool = False):
        tokens = batch["tokens"]
        logits, _, _ = rwkv6.forward_full(params, cfg, tokens, remat=remat)
        mask = batch.get("loss_mask")
        xe = softmax_xent(logits[:, :-1], tokens[:, 1:],
                          None if mask is None else mask[:, 1:])
        return xe, {"xent": xe}

    def prefill(params, batch, cache_len: Optional[int] = None):
        return rwkv6.prefill(params, cfg, batch["tokens"])

    def decode_step(params, cache, token, pos, **extras):
        return rwkv6.forward_decode(params, cfg, token, cache, pos)

    def init_cache(batch_size: int, seq: int):
        return rwkv6.init_state(cfg, batch_size)

    return Model(cfg, init, loss, prefill, decode_step, init_cache)


# --------------------------------------------------------------------------
# Zamba2 hybrid
# --------------------------------------------------------------------------
def _zamba_model(cfg: ModelConfig) -> Model:
    def init(rng):
        return mamba2.init_zamba(rng, cfg)

    def loss(params, batch, remat: bool = False):
        tokens = batch["tokens"]
        logits, _, _ = mamba2.forward_full(params, cfg, tokens, remat=remat)
        mask = batch.get("loss_mask")
        xe = softmax_xent(logits[:, :-1], tokens[:, 1:],
                          None if mask is None else mask[:, 1:])
        return xe, {"xent": xe}

    def prefill(params, batch, cache_len: Optional[int] = None):
        return mamba2.prefill(params, cfg, batch["tokens"], cache_len)

    def decode_step(params, cache, token, pos, **extras):
        return mamba2.forward_decode(params, cfg, token, cache, pos)

    def init_cache(batch_size: int, seq: int):
        return mamba2.init_state(cfg, batch_size, seq)

    return Model(cfg, init, loss, prefill, decode_step, init_cache)


# --------------------------------------------------------------------------
# Whisper (enc-dec)
# --------------------------------------------------------------------------
def _whisper_model(cfg: ModelConfig) -> Model:
    def init(rng):
        return whisper.init_model(rng, cfg)

    def loss(params, batch, remat: bool = False):
        tokens = batch["tokens"]
        logits, _, _ = whisper.forward_full(params, cfg, tokens,
                                            batch["audio_embeds"], remat=remat)
        mask = batch.get("loss_mask")
        xe = softmax_xent(logits[:, :-1], tokens[:, 1:],
                          None if mask is None else mask[:, 1:])
        return xe, {"xent": xe}

    def prefill(params, batch, cache_len: Optional[int] = None):
        tokens = batch["tokens"]
        logits, caches, _ = whisper.forward_full(
            params, cfg, tokens, batch["audio_embeds"], return_cache=True,
            last_only=True)
        self_kv = _relay_kv(caches.self_kv, cfg,
                            cache_len or tokens.shape[1])
        return logits[:, -1], whisper.WhisperCache(self_kv, caches.cross_kv)

    def decode_step(params, cache, token, pos, **extras):
        return whisper.forward_decode(params, cfg, token, cache, pos)

    def init_cache(batch_size: int, seq: int):
        return whisper.init_cache(cfg, batch_size, seq)

    return Model(cfg, init, loss, prefill, decode_step, init_cache)


# --------------------------------------------------------------------------
# Factory
# --------------------------------------------------------------------------
def build_model(cfg: ModelConfig) -> Model:
    if cfg.family in ("dense", "moe", "vlm"):
        return _decoder_model(cfg)
    if cfg.family == "ssm":
        return _rwkv_model(cfg)
    if cfg.family == "hybrid":
        return _zamba_model(cfg)
    if cfg.family == "encdec":
        return _whisper_model(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")


def synthetic_batch(cfg: ModelConfig, batch: int, seq: int, rng=None,
                    np_seed: int = 0) -> Dict[str, jnp.ndarray]:
    """A runnable (CPU) batch with the right extras for the family."""
    import numpy as np
    r = np.random.default_rng(np_seed)
    out: Dict[str, Any] = {
        "tokens": jnp.asarray(
            r.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)),
    }
    if cfg.family == "encdec":
        out["audio_embeds"] = jnp.asarray(
            r.normal(0, 1, (batch, cfg.encoder_seq, cfg.d_model)), cfg.dtype)
    if cfg.family == "vlm":
        n_patch = max(1, seq // 4)
        vm = np.zeros((batch, seq), bool)
        vm[:, :n_patch] = True
        out["vision_embeds"] = jnp.asarray(
            r.normal(0, 1, (batch, n_patch, cfg.d_model)), cfg.dtype)
        out["vision_mask"] = jnp.asarray(vm)
        # M-RoPE ids: vision patches share t=0 with (h, w) grid; text runs on
        tpos = np.zeros((batch, seq, 3), np.int32)
        side = max(1, int(np.sqrt(n_patch)))
        for i in range(n_patch):
            tpos[:, i] = (0, i // side, i % side)
        for i in range(n_patch, seq):
            t = i - n_patch + 1
            tpos[:, i] = (t, t, t)
        out["mrope_positions"] = jnp.asarray(tpos)
    return out
