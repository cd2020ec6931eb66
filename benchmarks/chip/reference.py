"""Plain float32 reference of the dense GQA decoder both configurations use.

RMSNorm (pre-norm), RoPE (half-split rotation), GQA attention with
optional QKV bias, SwiGLU FFN, final RMSNorm and a tied or untied head,
in ``jax.numpy`` at ``default_matmul_precision("highest")``, with the
weights made again from the seed (``weights.py``). It imports nothing of
the program and reads nothing the program made.

It runs one layer at a time, with the queries in blocks, so a 32K-token
sequence of a 5,120-wide model fits beside nothing else on one chip.

``fp8=True`` is the control: the same computation with both operands of
every matrix product rounded to float8 e4m3 (per-tensor scale), the
precision below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights

E4M3_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8, spec="...i,ij->...j"):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@functools.partial(jax.jit, static_argnames=("m", "qblock", "fp8"))
def _layer(p, x, m, qblock, fp8):
    """x [T, D]; rows past the sequence are padding, which causality
    keeps out of every real row."""
    T = x.shape[0]
    h, hk, dh = m.num_heads, m.num_kv_heads, m.head_dim
    g = h // hk
    pos = jnp.arange(T)
    a = p["attn"]
    y = _rms(x, p["ln_attn"], m.eps)
    q = _mm(y, a["wq"], fp8)
    k = _mm(y, a["wk"], fp8)
    v = _mm(y, a["wv"], fp8)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(T, h, dh), pos, m.theta).reshape(T, hk, g, dh)
    k = _rope(k.reshape(T, hk, dh), pos, m.theta)
    v = v.reshape(T, hk, dh)

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * qblock, qblock, 0)
        s = _mm(qb, k, fp8, "qhgd,khd->hgqk") / jnp.sqrt(jnp.float32(dh))
        qpos = b * qblock + jnp.arange(qblock)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, -1)
        return _mm(w, v, fp8, "hgqk,khd->qhgd")

    o = jax.lax.map(block, jnp.arange(T // qblock)).reshape(T, h * dh)
    x = x + _mm(o, a["wo"], fp8)
    f = p["ffn"]

    def ffn(xb):
        y = _rms(xb, p["ln_mlp"], m.eps)
        u = jax.nn.silu(_mm(y, f["w_gate"], fp8)) * _mm(y, f["w_up"], fp8)
        return xb + _mm(u, f["w_down"], fp8)

    # rows in blocks: the FFN's [T, d_ff] intermediates of a 32K-token
    # sequence would not fit beside the weights
    return jax.lax.map(ffn, x.reshape(T // qblock, qblock, -1)).reshape(
        T, -1)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _head(outer, x, m, fp8):
    y = _rms(x, outer["ln_f"], m.eps)
    w = outer["embed"].T if m.tie else outer["unembed"]
    return _mm(y, w, fp8)


class _Static:
    """Hashable view of the model sizes for ``jit``'s static arguments."""

    def __init__(self, m: dict):
        self.num_heads, self.num_kv_heads = m["num_heads"], m["num_kv_heads"]
        self.head_dim, self.eps = m["head_dim"], m["norm_eps"]
        self.theta, self.tie = m["rope_theta"], m["tie_embeddings"]
        self._key = (self.num_heads, self.num_kv_heads, self.head_dim,
                     self.eps, self.theta, self.tie)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Static) and self._key == other._key


_layer_weights = jax.jit(weights.layer_leaves, static_argnums=0)
_outer_weights = jax.jit(weights.outer_leaves, static_argnums=0)


class Reference:
    """Logits of whole sequences under the seed's weights."""

    def __init__(self, m: dict, seed: int, qblock: int = 256):
        self.m = m
        self.key = weights.root_key(seed)
        self.s = _Static(m)
        self.qblock = qblock
        self._hm = _Hashable(m)

    def logits(self, tokens: np.ndarray, start: int, fp8: bool = False):
        """float32 logits [len(tokens) - start, V] at positions start..end
        of ``tokens`` (each predicting the token after it)."""
        with jax.default_matmul_precision("highest"):
            T = len(tokens)
            # lengths padded to 1/8-octave steps: few compiled shapes, at
            # most 1/8 of the work wasted
            g = max(self.qblock, 1 << max(T.bit_length() - 3, 0))
            Tp = -(-T // g) * g
            ids = np.zeros(Tp, np.int32)
            ids[:T] = tokens
            # the embedding and head are made again at each end rather
            # than held through the layers
            x = _outer_weights(self._hm, self.key)["embed"][jnp.asarray(ids)]
            for layer in range(self.m["num_layers"]):
                p = _layer_weights(self._hm, self.key, layer)
                x = _layer(p, x, self.s, self.qblock, fp8)
                del p
            out = _head(_outer_weights(self._hm, self.key), x[start:T],
                        self.s, fp8)
            return np.asarray(out, np.float32)


class _Hashable(dict):
    """A model-size dict usable as a static ``jit`` argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))
