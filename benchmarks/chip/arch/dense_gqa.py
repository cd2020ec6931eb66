"""The dense GQA decoder: RMSNorm (pre-norm), RoPE (half-split rotation),
grouped-query attention with optional QKV bias, a SwiGLU FFN, a final
RMSNorm and a tied or untied head (Llama, Qwen2). One architecture
module of the benchmark, found by the name ``dense_gqa`` in a
configuration's ``architecture`` key (see ``harness.py`` for the
interface). It holds, for this architecture alone:

* ``dims``: the sizes the benchmark's arithmetic and reference use, from
  the configuration file's published keys;
* ``program_config``: the program's ``ModelConfig`` for those sizes (the
  one place this module touches the program, imported when called);
* weights: random from ``--seed``, made by the benchmark itself;
* ``Reference``: a plain float32 forward and its float8 control;
* the FLOP and byte arithmetic the metric readers call.

**Weights.** Every leaf comes from its own key, folded from the seed,
the layer and the leaf's name, so the reference can make any one layer
again, in float32, without taking anything from the program. The program
gets the whole tree in the layout its dense decoder stacks (a leading
layer axis on every per-layer leaf), made on the device in one jitted
call, in the type it serves, and where the caller gives shardings,
straight into them. Scales: matrices N(0, 1/fan_in); the embedding
N(0, 1/d_model), so tied and untied heads give logits of about unit
spread; RMSNorm weights 1 + 0.1 N(0, 1) and QKV biases 0.1 N(0, 1), so
that a path that drops either shows in the logits.

**Reference.** ``jax.numpy`` at ``default_matmul_precision("highest")``,
with the weights made again from the seed. It imports nothing of the
program and reads nothing the program made. It runs one layer at a time,
with the queries in blocks, so a 32K-token sequence of a 5,120-wide
model fits beside nothing else on one chip. ``fp8=True`` is the control:
the same computation with both operands of every matrix product rounded
to float8 e4m3 (per-tensor scale), the precision below the bfloat16 the
configurations state.

**Arithmetic** (kept apart from the program so a change to the program
cannot move the yardstick):

* model FLOPs of a token: 2 x the matmul parameters it passes through
  (Q/K/V/O projections, the SwiGLU FFN, and the LM head where the step
  computes logits for it; the embedding gather is not a matmul), plus
  attention over the token's actual context: 4 x heads x head_dim x ctx
  per layer (QK^T and PV, 2 FLOPs per multiply-add);
* least time of one paged-attention call (one layer of one step): the
  larger of its FLOPs over the bf16 peak and the K/V bytes its segments
  must read over HBM bandwidth. Bytes count ``head_dim`` elements per
  row, not the 128-lane padded pool rows, so a change that stops reading
  padding can raise the share. The peaks are those of all the chips that
  share the call (the reader passes them).
"""
from __future__ import annotations

import functools
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------
def dims(config: dict) -> dict:
    """The sizes the benchmark's arithmetic and reference use, from the
    configuration file's published keys."""
    c = config
    heads = c["num_attention_heads"]
    return {
        "num_layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "num_heads": heads,
        "num_kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim") or c["hidden_size"] // heads,
        "d_ff": c["intermediate_size"],
        "vocab_size": c["vocab_size"],
        "qkv_bias": bool(c.get("attention_bias",
                               c.get("model_type") == "qwen2")),
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "dtype": c["serve"]["dtype"],
    }


def program_config(name: str, m: dict, max_position: int):
    from repro.models.common import ModelConfig
    return ModelConfig(
        name=name, family="dense", num_layers=m["num_layers"],
        d_model=m["d_model"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        qkv_bias=m["qkv_bias"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], tie_embeddings=m["tie_embeddings"],
        max_position=max_position, dtype=jnp.dtype(m["dtype"]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
_EMBED, _UNEMBED, _LN_F, _LAYERS = 0, 1, 2, 3


def root_key(seed: int):
    # the low 32 bits seed the key and the rest are folded in, so seeds
    # past 32 bits stay distinct
    seed = int(seed) % 2**63
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _normal(key, shape, std, mean=0.0):
    return jax.random.normal(key, shape, jnp.float32) * std + mean


def layer_leaves(m: dict, key, layer):
    """One layer's leaves in float32, rounded to the served dtype's
    values (the reference computes with exactly what is served)."""
    d, h, hk, dh, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    k = jax.random.fold_in(jax.random.fold_in(key, _LAYERS), layer)

    def leaf(i, shape, std, mean=0.0):
        return _normal(jax.random.fold_in(k, i), shape, std, mean)

    attn = {"wq": leaf(0, (d, h * dh), d ** -0.5),
            "wk": leaf(1, (d, hk * dh), d ** -0.5),
            "wv": leaf(2, (d, hk * dh), d ** -0.5),
            "wo": leaf(3, (h * dh, d), (h * dh) ** -0.5)}
    if m["qkv_bias"]:
        attn.update(bq=leaf(4, (h * dh,), 0.1), bk=leaf(5, (hk * dh,), 0.1),
                    bv=leaf(6, (hk * dh,), 0.1))
    p = {"ln_attn": leaf(7, (d,), 0.1, 1.0), "ln_mlp": leaf(8, (d,), 0.1, 1.0),
         "attn": attn,
         "ffn": {"w_gate": leaf(9, (d, f), d ** -0.5),
                 "w_up": leaf(10, (d, f), d ** -0.5),
                 "w_down": leaf(11, (f, d), f ** -0.5)}}
    return _served(p, m)


def outer_leaves(m: dict, key):
    """Embedding, final norm and (untied) head, float32 of served values."""
    d, v = m["d_model"], m["vocab_size"]
    p = {"embed": _normal(jax.random.fold_in(key, _EMBED), (v, d), d ** -0.5),
         "ln_f": _normal(jax.random.fold_in(key, _LN_F), (d,), 0.1, 1.0)}
    if not m["tie_embeddings"]:
        p["unembed"] = _normal(jax.random.fold_in(key, _UNEMBED), (d, v),
                               d ** -0.5)
    return _served(p, m)


def _served(tree, m):
    dt = jnp.dtype(m["dtype"])
    return jax.tree.map(lambda a: a.astype(dt).astype(jnp.float32), tree)


def make_params(m: dict, seed: int, shardings=None):
    """The served tree, on the device, in one jitted call. ``shardings``
    (a tree of ``jax.sharding.Sharding`` like the params) are the call's
    ``out_shardings``: each device then makes only its share. The values
    do not depend on them (``jax_threefry_partitionable``)."""
    dt = jnp.dtype(m["dtype"])

    def make(key):
        layers = jax.lax.map(lambda l: jax.tree.map(
            lambda a: a.astype(dt), layer_leaves(m, key, l)),
            jnp.arange(m["num_layers"]))
        out = jax.tree.map(lambda a: a.astype(dt), outer_leaves(m, key))
        out["layers"] = layers
        return out

    fn = (jax.jit(make) if shardings is None
          else jax.jit(make, out_shardings=shardings))
    return fn(root_key(seed))


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------
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


_layer_weights = jax.jit(layer_leaves, static_argnums=0)
_outer_weights = jax.jit(outer_leaves, static_argnums=0)


class Reference:
    """Logits of whole sequences under the seed's weights."""

    def __init__(self, m: dict, seed: int, qblock: int = 256):
        self.m = m
        self.key = root_key(seed)
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


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def layer_matmul_params(m: dict) -> int:
    d, h, hk, dh, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    attn = d * h * dh + 2 * d * hk * dh + h * dh * d
    return attn + 3 * d * f


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, ctx: int) -> int:
    """QK^T and PV FLOPs of ONE query token over ``ctx`` keys, all layers."""
    return 4 * m["num_heads"] * m["head_dim"] * int(ctx) * m["num_layers"]


def token_flops(m: dict, ctx: int, logits: bool) -> int:
    """Model FLOPs of one token attending over ``ctx`` keys (itself
    included); ``logits`` adds the LM head."""
    f = 2 * layer_matmul_params(m) * m["num_layers"] + attn_flops(m, ctx)
    return f + (2 * head_params(m) if logits else 0)


def chunk_flops(m: dict, ctx0: int, clen: int, logits: bool) -> int:
    """Model FLOPs of a prompt chunk of ``clen`` tokens starting after
    ``ctx0`` cached tokens (token i attends over ctx0 + i + 1 keys)."""
    keys = clen * ctx0 + clen * (clen + 1) // 2
    f = 2 * layer_matmul_params(m) * m["num_layers"] * clen
    f += 4 * m["num_heads"] * m["head_dim"] * keys * m["num_layers"]
    return f + (2 * head_params(m) if logits else 0)


def attn_call_cost(m: dict, decode_ctx: Iterable[int],
                   chunks: Iterable[Tuple[int, int]],
                   kv_bytes_per_elem: int = 2) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) of ONE layer's paged-attention call in a step:
    decode segments attend over ``ctx`` keys each; a chunk ``(ctx0,
    clen)`` attends causally over ``ctx0 + i + 1`` keys for its token i
    and reads its ``ctx0 + clen`` K/V rows once. Bytes: K and V rows
    (``head_dim`` wide) plus the q and o tiles, in bf16."""
    h, hk, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = 0
    rows = 0          # K/V rows read, per kv head
    q_rows = 0
    for ctx in decode_ctx:
        flops += 4 * h * dh * int(ctx)
        rows += int(ctx)
        q_rows += 1
    for ctx0, clen in chunks:
        keys = clen * ctx0 + clen * (clen + 1) // 2
        flops += 4 * h * dh * keys
        rows += ctx0 + clen
        q_rows += clen
    byts = 2 * rows * hk * dh * kv_bytes_per_elem + 2 * q_rows * h * dh * 2
    return flops, byts


def attn_least_time(m: dict, decode_ctx, chunks, peak_flops: float,
                    hbm_bw: float) -> float:
    """Least time of one step's paged attention over all layers: each
    layer is one call, so the step's least time is layers x the call's."""
    flops, byts = attn_call_cost(m, decode_ctx, chunks)
    return m["num_layers"] * max(flops / peak_flops, byts / hbm_bw)
