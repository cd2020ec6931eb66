"""Random weights from ``--seed``, made by the benchmark itself.

Every leaf comes from its own key, folded from the seed, the layer and
the leaf's name, so the reference can make any one layer again, in
float32, without taking anything from the program. The program gets the
whole tree in the layout its dense decoder stacks (a leading layer axis
on every per-layer leaf), made on the device in one jitted call, in the
type it serves.

Scales: matrices N(0, 1/fan_in); the embedding N(0, 1/d_model), so tied
and untied heads give logits of about unit spread; RMSNorm weights
1 + 0.1 N(0, 1) and QKV biases 0.1 N(0, 1), so that a path that drops
either shows in the logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

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


def make_params(m: dict, seed: int):
    """The served tree, on the device, in one jitted call."""
    dt = jnp.dtype(m["dtype"])

    def make(key):
        layers = jax.lax.map(lambda l: jax.tree.map(
            lambda a: a.astype(dt), layer_leaves(m, key, l)),
            jnp.arange(m["num_layers"]))
        out = jax.tree.map(lambda a: a.astype(dt), outer_leaves(m, key))
        out["layers"] = layers
        return out

    return jax.jit(make)(root_key(seed))
