"""GSPMD sharding rules for every architecture family.

Name-based rules map parameter pytree paths to PartitionSpecs: tensor-
parallel weights shard on ``model`` (attention heads / FFN dim / expert
axis), batch shards on ``('pod','data')``, decode KV caches shard batch on
``data`` and heads (or head_dim when head count doesn't divide) on
``model``; ``long_500k`` context-parallel decode shards the cache
*sequence* axis on ``data``.

Every rule is divisibility-guarded — jax rejects non-divisible shardings —
falling back to replication for that dim.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import batch_axes
from repro.models.common import ModelConfig

# (path regex, dim index from the END to shard on "model")
PARAM_RULES: Sequence[Tuple[str, int]] = (
    (r"(^|/)embed$", 2),                 # [V, D] -> shard V
    (r"(^|/)unembed$", 1),               # [D, V] -> shard V
    (r"moe/router$", -1),                # replicated (tiny, f32)
    (r"moe/w_(gate|up|down)$", 3),       # [L, E, D, F] -> expert parallel
    (r"attn/w[qkv]$", 1),
    (r"attn/b[qkv]$", 1),
    (r"attn/wo$", 2),
    (r"ffn/w_(gate|up)$", 1),
    (r"ffn/b_up$", 1),
    (r"ffn/w_down$", 2),
    # rwkv6
    (r"(^|/)w_[rkvg]$", 1),
    (r"(^|/)w_o$", 2),
    (r"(^|/)cw_[kr]$", 1),
    (r"(^|/)cw_v$", 2),
    # zamba2 mamba blocks
    (r"mamba/w_in$", 1),
    (r"mamba/conv_w$", 1),
    (r"mamba/conv_b$", 1),
    (r"mamba/ln_gate$", 1),
    (r"mamba/w_out$", 2),
)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec(path: str, shape: Tuple[int, ...], model_size: int,
               *, expert_2d: bool = False, data_size: int = 0) -> P:
    for pat, dim_from_end in PARAM_RULES:
        if re.search(pat, path):
            if dim_from_end < 0:
                return P()
            d = len(shape) - dim_from_end
            spec: list = [None] * len(shape)
            if 0 <= d < len(shape) and shape[d] % model_size == 0:
                spec[d] = "model"
            if expert_2d and re.search(r"moe/w_(gate|up|down)$", path):
                # §Perf beyond-paper: experts on 'model' AND the FFN dim on
                # 'data' — per-chip expert weights shrink by the data size
                ffn_d = len(shape) - (1 if path.endswith(("w_gate", "w_up"))
                                      else 2)
                if (spec[ffn_d] is None and data_size
                        and shape[ffn_d] % data_size == 0):
                    spec[ffn_d] = "data"
            if all(a is None for a in spec):
                return P()
            return P(*spec)
    return P()


def param_shardings(param_shapes, mesh, *, expert_2d: bool = False) -> Any:
    model_size = mesh.shape["model"]
    data_size = mesh.shape.get("data", 1)
    flat, tdef = jax.tree_util.tree_flatten_with_path(param_shapes)
    specs = [NamedSharding(mesh,
                           param_spec(_path_str(p), tuple(l.shape),
                                      model_size, expert_2d=expert_2d,
                                      data_size=data_size))
             for p, l in flat]
    return jax.tree_util.tree_unflatten(tdef, specs)


def zero1_shardings(param_shapes, mesh, base: Any = None) -> Any:
    """ZeRO-1 (§Perf beyond-paper): optimizer mu/nu additionally shard
    their largest replicated dim over 'data'. Params keep ``base``."""
    model_size = mesh.shape["model"]
    data_size = mesh.shape.get("data", 1)
    flat, tdef = jax.tree_util.tree_flatten_with_path(param_shapes)
    specs = []
    for p, leaf in flat:
        spec = list(param_spec(_path_str(p), tuple(leaf.shape), model_size))
        spec += [None] * (len(leaf.shape) - len(spec))
        # shard the largest still-replicated dim on 'data'
        cands = [(dim, i) for i, (dim, ax) in
                 enumerate(zip(leaf.shape, spec))
                 if ax is None and dim % data_size == 0 and dim >= data_size]
        if cands:
            _, i = max(cands)
            spec[i] = "data"
        specs.append(NamedSharding(mesh, P(*spec)))
    return jax.tree_util.tree_unflatten(tdef, specs)


# --------------------------------------------------------------------------
# Batch / cache shardings
# --------------------------------------------------------------------------
def _guard(shape, spec_list, mesh) -> P:
    """Drop sharded dims that don't divide."""
    out = []
    for dim, ax in zip(shape, spec_list):
        if ax is None:
            out.append(None)
            continue
        size = int(np.prod([mesh.shape[a] for a in
                            (ax if isinstance(ax, tuple) else (ax,))]))
        out.append(ax if dim % size == 0 else None)
    return P(*out)


def batch_shardings(batch_shapes, mesh) -> Any:
    """Shard dim 0 (global batch) of every input on ('pod','data')."""
    ba = batch_axes(mesh)

    def one(leaf):
        spec = [ba] + [None] * (len(leaf.shape) - 1)
        return NamedSharding(mesh, _guard(leaf.shape, spec, mesh))

    return jax.tree.map(one, batch_shapes)


def cache_shardings(cache_shapes, mesh, *, batch_size: int,
                    cache_seq: int, context_parallel: bool = False,
                    seq_on_model: bool = False) -> Any:
    """Decode KV/state-cache sharding.

    Axes are located by SIZE, not position (cache layouts differ per
    family): the batch axis is the first non-leading dim equal to
    ``batch_size``; the sequence axis is the first dim equal to
    ``cache_seq``. Strategy:
      * batch -> 'data' (normal decode),
      * ``context_parallel`` (long_500k, B=1): sequence -> 'data' instead,
      * a 'model'-divisible later dim (heads, else head_dim) -> 'model'.
    """
    data_size = mesh.shape["data"]
    model_size = mesh.shape["model"]

    def one(leaf):
        shape = leaf.shape
        r = len(shape)
        spec: list = [None] * r
        data_ax = None
        if context_parallel:
            for i, d in enumerate(shape):
                if d == cache_seq and d % data_size == 0:
                    data_ax = i
                    break
        else:
            for i in range(1, r):
                if shape[i] == batch_size and shape[i] % data_size == 0:
                    data_ax = i
                    break
        if data_ax is not None:
            spec[data_ax] = "data"
        # model axis preference: heads (conflict-free GQA) > sequence
        # (partial-softmax stats are tiny — §Perf) > head_dim (forces a
        # cache-sized all-gather for the QK contraction; naive baseline
        # fallback). ``seq_on_model`` enables the sequence option.
        start = (data_ax + 1) if data_ax is not None else 1
        non_seq = [i for i in range(start, r) if spec[i] is None
                   and shape[i] != cache_seq]
        heads = [i for i in non_seq if i < r - 1]
        seq = ([i for i in range(start, r) if spec[i] is None
                and shape[i] == cache_seq] if seq_on_model else [])
        final = [i for i in non_seq if i == r - 1]
        for i in heads + seq + final:
            if shape[i] % model_size == 0:
                spec[i] = "model"
                break
        return NamedSharding(mesh, _guard(shape, spec, mesh))

    return jax.tree.map(one, cache_shapes)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# --------------------------------------------------------------------------
# Serving tensor parallelism (DESIGN.md §Sharded serving)
# --------------------------------------------------------------------------
# The training PARAM_RULES already express the serving TP layout for every
# dense decoder weight: wq/wk/wv/b* split the head dim, wo splits its
# H·Dh contraction dim, ffn w_gate/w_up/b_up split F, w_down splits its F
# contraction dim, embed splits V (rows) and unembed splits V (columns) —
# exactly the manual-collective contract the tp_axis forwards implement.
# Only MoE differs: training shards the EXPERT axis (all-to-all dispatch),
# while the serving engine keeps every expert on every shard and splits
# the per-expert FFN dim F (router replicated) so moe_dense needs just
# one psum after the w_down contraction.
_SERVING_OVERRIDES: Sequence[Tuple[str, int]] = (
    (r"moe/router$", -1),                # replicated
    (r"moe/w_(gate|up)$", 1),            # [L, E, D, F] -> split F
    (r"moe/w_down$", 2),                 # [L, E, F, D] -> split F
)


def serving_param_spec(path: str, shape: Tuple[int, ...],
                       model_size: int) -> P:
    """PartitionSpec of one serving parameter under tensor parallelism.
    Non-divisible dims replicate (the Engine asserts divisibility of the
    dims that MUST split — kv heads and vocab)."""
    for pat, dim_from_end in _SERVING_OVERRIDES:
        if re.search(pat, path):
            if dim_from_end < 0:
                return P()
            d = len(shape) - dim_from_end
            spec: list = [None] * len(shape)
            if 0 <= d < len(shape) and shape[d] % model_size == 0:
                spec[d] = "model"
            return P(*spec) if any(spec) else P()
    return param_spec(path, shape, model_size)


def serving_param_spec_tree(params, tp: int) -> Any:
    """PartitionSpec pytree for a serving param tree at TP size ``tp``."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    specs = [serving_param_spec(_path_str(p), tuple(l.shape), tp)
             for p, l in flat]
    return jax.tree_util.tree_unflatten(tdef, specs)


def _kv_head_specs(tree, axis: int) -> Any:
    def one(leaf):
        nd = len(leaf.shape)
        assert nd >= 4, f"kv leaf rank {nd} < 4"
        spec = [None] * nd
        spec[axis] = "model"
        return P(*spec)
    return jax.tree.map(one, tree)


def pool_spec_tree(pool) -> Any:
    """PartitionSpec pytree for a paged KV pool: the kv-head axis — dim 2
    of [L, NB, Hkv, BS, Dp] rows and of [L, NB, Hkv, BS] int8 scales —
    shards on 'model'; block ids, work lists and every other axis stay
    replicated, so the allocator, prefix index and migration bookkeeping
    never see the mesh."""
    return _kv_head_specs(pool, 2)


def piece_spec_tree(piece) -> Any:
    """PartitionSpec pytree for a contiguous KV piece (the migration wire
    format, [L, 1, T, Hkv, Dh] rows and [L, 1, T, Hkv] scales): dim 3,
    the kv-head axis, shards on 'model'."""
    return _kv_head_specs(piece, 3)
