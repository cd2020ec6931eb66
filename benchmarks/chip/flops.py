"""Operation and byte counts of the served model, from its shapes alone.

The benchmark's own arithmetic (kept apart from the program so a change
to the program cannot move the yardstick):

* model FLOPs of a token: 2 x the matmul parameters it passes through
  (Q/K/V/O projections, the SwiGLU FFN, and the LM head where the step
  computes logits for it; the embedding gather is not a matmul), plus
  attention over the token's actual context: 4 x heads x head_dim x ctx
  per layer (QK^T and PV, 2 FLOPs per multiply-add);
* least time of one paged-attention call (one layer of one step): the
  larger of its FLOPs over the bf16 peak and the K/V bytes its segments
  must read over HBM bandwidth. Bytes count ``head_dim`` elements per
  row, not the 128-lane padded pool rows, so a change that stops reading
  padding can raise the share.
"""
from __future__ import annotations

from typing import Iterable, Tuple


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
