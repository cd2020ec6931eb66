"""Cross-path numerical consistency: prefill+decode == full forward,
MoE dispatch equivalence, sliding-window semantics, flash == naive."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, synthetic_batch
from repro.models import mamba2, rwkv6, transformer, whisper
from repro.models.attention import (_causal_mask, _gqa_sdpa,
                                    flash_attention_xla)
from repro.models.mlp import init_moe, moe_dense, moe_gshard

CONSISTENCY_ARCHS = ["smollm-360m", "qwen3-moe-30b-a3b", "qwen2-vl-7b",
                     "rwkv6-7b", "zamba2-2.7b", "whisper-large-v3"]


def _full_logits(cfg, params, batch):
    if cfg.family in ("dense", "moe", "vlm"):
        out, _, _ = transformer.forward_full(
            params, cfg, batch["tokens"],
            vision_embeds=batch.get("vision_embeds"),
            vision_mask=batch.get("vision_mask"),
            mrope_positions=batch.get("mrope_positions"))
    elif cfg.family == "ssm":
        out, _, _ = rwkv6.forward_full(params, cfg, batch["tokens"])
    elif cfg.family == "hybrid":
        out, _, _ = mamba2.forward_full(params, cfg, batch["tokens"])
    else:
        out, _, _ = whisper.forward_full(params, cfg, batch["tokens"],
                                         batch["audio_embeds"])
    return out


@pytest.mark.parametrize("arch", CONSISTENCY_ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, T = 2, 12
    full = synthetic_batch(cfg, B, T + 1)
    pre = {k: (v[:, :T] if k in ("tokens", "vision_mask", "mrope_positions")
               else v) for k, v in full.items()}
    _, cache = model.prefill(params, pre, cache_len=T + 4)
    pos = jnp.full((B,), T, jnp.int32)
    extras = ({"mrope_positions": full["mrope_positions"][:, T:T + 1]}
              if cfg.use_mrope else {})
    dec, _ = model.decode_step(params, cache, full["tokens"][:, T], pos,
                               **extras)
    ref = _full_logits(cfg, params, full)[:, -1]
    np.testing.assert_allclose(np.asarray(dec), np.asarray(ref),
                               atol=5e-5, rtol=5e-4)


def test_moe_gshard_matches_dense_f64():
    with jax.enable_x64(True):
        cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b").reduced(),
                                  dtype=jnp.float64)
        p = init_moe(jax.random.PRNGKey(1), cfg)
        p = jax.tree.map(lambda a: a.astype(jnp.float64), p)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model),
                              jnp.float64)
        yd, auxd = moe_dense(p, cfg, x)
        yg, auxg = moe_gshard(p, cfg, x, capacity_factor=8.0)
        np.testing.assert_allclose(np.asarray(yd), np.asarray(yg),
                                   atol=1e-10)
        assert float(auxd) == pytest.approx(float(auxg))


def test_moe_gshard_drops_over_capacity():
    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    p = init_moe(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, cfg.d_model))
    y_tight, _ = moe_gshard(p, cfg, x, capacity_factor=0.25)
    y_large, _ = moe_gshard(p, cfg, x, capacity_factor=8.0)
    assert not np.allclose(np.asarray(y_tight), np.asarray(y_large))


def test_sliding_window_decode_matches_full_for_short_seq():
    """Window ≥ sequence length -> sliding == full attention."""
    base = get_config("smollm-360m").reduced()
    win = dataclasses.replace(base, sliding_window=64)
    m_full = build_model(base)
    m_win = build_model(win)
    params = m_full.init(jax.random.PRNGKey(0))
    batch = synthetic_batch(base, 2, 10)
    _, c1 = m_full.prefill(params, batch, cache_len=32)
    _, c2 = m_win.prefill(params, batch, cache_len=32)
    pos = jnp.full((2,), 10, jnp.int32)
    tok = batch["tokens"][:, 0]
    l1, _ = m_full.decode_step(params, c1, tok, pos)
    l2, _ = m_win.decode_step(params, c2, tok, pos)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=5e-5, rtol=5e-4)


def test_sliding_window_forgets_distant_tokens():
    base = get_config("smollm-360m").reduced()
    win = dataclasses.replace(base, sliding_window=4)
    m = build_model(win)
    params = m.init(jax.random.PRNGKey(0))
    batch = synthetic_batch(win, 1, 12)
    # perturb the FIRST token: with window 4 and prefill of 12, the decode
    # at pos 12 must be unaffected
    b2 = dict(batch)
    t2 = np.asarray(batch["tokens"]).copy()
    t2[0, 0] = (t2[0, 0] + 1) % win.vocab_size
    b2["tokens"] = jnp.asarray(t2)
    _, c1 = m.prefill(params, batch, cache_len=16)
    _, c2 = m.prefill(params, b2, cache_len=16)
    pos = jnp.full((1,), 12, jnp.int32)
    tok = jnp.asarray([5], jnp.int32)
    l1, _ = m.decode_step(params, c1, tok, pos)
    l2, _ = m.decode_step(params, c2, tok, pos)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-6)


def test_flash_equals_naive_attention():
    rng = np.random.default_rng(3)
    B, T, H, Hkv, Dh = 2, 257, 8, 2, 64   # odd T exercises padding
    q = jnp.asarray(rng.normal(0, 1, (B, T, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, T, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, T, Hkv, Dh)), jnp.float32)
    for window in (0, 32):
        ref = _gqa_sdpa(q, k, v, _causal_mask(T, T, 0, window))
        out = flash_attention_xla(q, k, v, causal=True, window=window,
                                  block_q=64, block_k=96)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)


def test_flash_gradients_finite():
    rng = np.random.default_rng(4)
    B, T, H, Hkv, Dh = 1, 128, 4, 2, 32
    q = jnp.asarray(rng.normal(0, 1, (B, T, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, T, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, T, Hkv, Dh)), jnp.float32)

    def f(q, k, v):
        return flash_attention_xla(q, k, v, block_q=32, block_k=32).sum()

    grads = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert bool(jnp.isfinite(g).all())
