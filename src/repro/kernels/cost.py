"""Block-level cost model for the decode-attention kernel on TPU.

This is the analytic bridge between the kernel and the rest of the system:
  * the Fig.-2 benchmark uses it to quantify the heterogeneity tax,
  * the simulator's ground-truth iteration cost is calibrated from it,
  * §Perf napkin math reads straight off these terms.

Model (per decode iteration, per chip):
  padded backend:  blocks(b) = ceil(S_pad / BS) for every request
  ragged backend:  blocks(b) = ceil(L_b / BS) compute + skip-overhead

Each KV block costs DMA ``2·BS·Dh·bytes / HBM_bw`` (K and V streamed
HBM→VMEM) and MXU ``2·2·G·BS·Dh / peak`` FLOP-time; decode attention has
arithmetic intensity ≈ G (<< ridge point), so the DMA term dominates and a
block's wall time is max(dma, mxu) ≈ dma — which is why wasted *padded*
blocks hurt exactly in proportion to their count, matching the paper's
observation that heterogeneity, not raw FLOPs, sets the iteration time.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

# TPU v5e constants (assignment-specified)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
SKIP_OVERHEAD_S = 2e-7       # per skipped grid step (scalar branch + DMA mgmt)
LAUNCH_OVERHEAD_S = 2e-6     # per EXTRA kernel launch beyond the first
                             # (dispatch + grid setup + scalar prefetch)
HOST_STAGING_BW = 16e9       # bytes/s host<->device staging (PCIe-class
                             # link the multi-tier KV demote/promote copies
                             # ride — DESIGN.md §Multi-tier KV)
PROMOTE_TOKEN_COST = 0.25    # routing price of one host-tier cached token:
                             # the h2d copy is ~4x cheaper than recomputing
                             # the token's prefill, so a host hit is priced
                             # as a quarter-length prompt tail


def h2d_block_time_s(block_bytes: float) -> float:
    """Wall time to stage ONE KV block across the host link (either
    direction — demote d2h and promote h2d ride the same staging path):
    a launch-sized dispatch overhead plus the payload at staging
    bandwidth."""
    return LAUNCH_OVERHEAD_S + float(block_bytes) / HOST_STAGING_BW


def promote_cost_tokens(n_blocks: int, block_size: int) -> float:
    """Token-equivalent ROUTING price of promoting ``n_blocks`` host-tier
    blocks: a host hit is cheaper than recompute but not free, so
    routing's effective length charges ``uncached_tail + this`` instead
    of treating the hit like a device hit. Pure and deterministic — the
    real server and the simulator call it with identical inputs, which
    is what keeps their decision logs in lockstep (DESIGN.md §Multi-tier
    KV)."""
    return PROMOTE_TOKEN_COST * float(n_blocks) * float(block_size)


def kv_bytes_per_elem(kv_dtype: str, head_dim: int) -> float:
    """HBM bytes per stored KV element. int8 carries one f32 scale per
    (position, kv-head) row amortized over the head dim —
    ``(Dh + 4)/Dh`` bytes, ≈ 1.94× denser than bf16 at Dh = 128
    (DESIGN.md §Quantized KV blocks)."""
    if kv_dtype == "int8":
        return 1.0 + 4.0 / head_dim
    assert kv_dtype == "bf16", kv_dtype
    return 2.0


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    num_q_heads: int
    num_kv_heads: int
    head_dim: int
    kv_bytes: float = 2.0      # bf16 cache; kv_bytes_per_elem for int8
    block_s: int = 512


def block_time_s(spec: AttnSpec) -> float:
    """Wall time of one (kv-head, kv-block) grid step."""
    g = spec.num_q_heads // spec.num_kv_heads
    dma = 2 * spec.block_s * spec.head_dim * spec.kv_bytes / HBM_BW
    mxu = 2 * 2 * g * spec.block_s * spec.head_dim / PEAK_FLOPS
    return max(dma, mxu)


def padded_blocks(lengths: Sequence[int], block_s: int,
                  pad_to: int | None = None) -> int:
    """Grid steps a padded (paper-faithful) backend executes per kv head."""
    if not len(lengths):
        return 0
    s_pad = pad_to if pad_to is not None else max(lengths)
    return len(lengths) * math.ceil(max(s_pad, 1) / block_s)


def ragged_blocks(lengths: Sequence[int], block_s: int) -> int:
    """Compute blocks a ragged backend executes per kv head."""
    return sum(math.ceil(max(l, 1) / block_s) for l in lengths)


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (bucketing policy shared by the flat
    grid, the engine's table width, and prefill prompt padding)."""
    p = 1
    while p < n:
        p *= 2
    return p


def flat_grid_blocks(lengths: Sequence[int], block_s: int,
                     bucketed: bool = True) -> int:
    """Grid steps the work-flattened backend executes per kv head: the
    real Σ_b ceil(L_b/BS) work items, padded to a pow2 bucket (padding
    items skip compute but still take a grid step — the flat analogue of
    SKIP_OVERHEAD_S, bounded at < 2x by the bucketing)."""
    n = ragged_blocks(lengths, block_s)
    return pow2_bucket(n) if (bucketed and n) else n


def decode_attn_time_flat_s(lengths: Sequence[int], spec: AttnSpec) -> float:
    """Decode-attention wall time for the work-flattened grid: unlike the
    ragged (B, Hkv, NBT) grid, no request pays another's block count — the
    only overhead is the pow2 bucket's padding tail."""
    if not len(lengths):
        return 0.0
    comp = ragged_blocks(lengths, spec.block_s)
    skipped = flat_grid_blocks(lengths, spec.block_s) - comp
    return spec.num_kv_heads * (comp * block_time_s(spec)
                                + skipped * SKIP_OVERHEAD_S)


def decode_attn_time_s(lengths: Sequence[int], spec: AttnSpec,
                       ragged: bool = False,
                       pad_to: int | None = None) -> float:
    """Decode-attention wall time for one iteration over a batch."""
    if not len(lengths):
        return 0.0
    t_blk = block_time_s(spec)
    full = padded_blocks(lengths, spec.block_s, pad_to)
    if not ragged:
        return spec.num_kv_heads * full * t_blk
    comp = ragged_blocks(lengths, spec.block_s)
    skipped = full - comp
    return spec.num_kv_heads * (comp * t_blk + skipped * SKIP_OVERHEAD_S)


# --------------------------------------------------------------------------
# Chunked prefill + mixed iterations (DESIGN.md §Chunked prefill) — the
# analytic mirror of kernels/prefill_attention.paged_prefill_attention and
# the engine's token-budgeted mixed step; sim/costmodel builds its ground
# truth from these instead of its own I² formula.
# --------------------------------------------------------------------------
def prefill_chunk_blocks(chunk: int, ctx: int, block_s: int) -> int:
    """Grid steps (per kv head) the chunked-prefill kernel runs for one
    chunk: every block of the written context plus the chunk itself."""
    return math.ceil(max(ctx + chunk, 1) / block_s)


def prefill_chunk_flops(chunk: int, ctx: int, spec: AttnSpec) -> float:
    """Attention MXU FLOPs of ONE prompt chunk at one layer: score + PV
    matmuls of ``chunk`` queries against the written context plus the
    (block-causally pruned) own chunk. Summing over a prompt's chunks
    recovers the causal whole-prompt count ≈ 2·H·Dh·I², so a single
    chunk=I call prices the monolithic prefill too — one formula, every
    granularity."""
    own = (chunk + spec.block_s) / 2.0        # causal prune within the chunk
    return 4.0 * spec.num_q_heads * spec.head_dim * chunk * (ctx + own)


def prefill_flops(input_len: int, spec: AttnSpec,
                  cached_tokens: int = 0) -> float:
    """Attention MXU FLOPs one layer spends prefilling a prompt of which
    ``cached_tokens`` leading tokens are already resident in the prefix
    cache (DESIGN.md §Prefix cache): only the uncached tail runs, as one
    logical chunk attending to the cached context plus itself. With
    ``cached_tokens=0`` this is the whole-prompt causal count."""
    cached = min(int(cached_tokens), max(int(input_len) - 1, 0))
    return prefill_chunk_flops(int(input_len) - cached, cached, spec)


def prefill_flops_skipped(input_len: int, cached_tokens: int,
                          spec: AttnSpec) -> float:
    """FLOPs a warm prefill never runs vs. a cold one — the benchmark's
    prefill-FLOPs-skipped counter (`benchmarks/bench_prefix_cache.py`)."""
    return (prefill_flops(input_len, spec)
            - prefill_flops(input_len, spec, cached_tokens))


def prefill_chunk_attn_time_s(chunk: int, ctx: int, spec: AttnSpec) -> float:
    """Wall time of one chunk's paged-prefill attention: DMA of the
    context blocks (HBM→VMEM, per kv head) vs. the chunk's MXU time —
    compute-bound for real chunk sizes, DMA-bound when a tiny chunk drags
    a huge context (which is why the engine packs chunks to a budget)."""
    blocks = prefill_chunk_blocks(chunk, ctx, spec.block_s)
    dma = (spec.num_kv_heads * blocks
           * 2 * spec.block_s * spec.head_dim * spec.kv_bytes / HBM_BW)
    mxu = prefill_chunk_flops(chunk, ctx, spec) / PEAK_FLOPS
    return max(dma, mxu)


def mixed_iter_time_s(chunks: Sequence[tuple], decode_lengths: Sequence[int],
                      spec: AttnSpec, *,
                      decode_backend: str = "flat") -> float:
    """Attention wall time of one token-budgeted MIXED iteration: the
    decode batch plus every packed prompt chunk ``(chunk_len, ctx_len)``
    — the analytic mirror of the engine's fused step (decode burst +
    chunked prefill, one device round-trip). ``decode_backend`` picks the
    decode term's kernel model (``fused`` | ``flat`` | ``ragged`` |
    ``padded``) so a chunked-vs-monolithic comparison can hold the decode
    backend fixed and attribute only the prefill difference to chunking.

    ``fused`` prices the single tagged work list: one launch over the real
    decode and chunk items, with no padding tail. The separate backends
    pay the decode and chunk grids' pow2 padding tails PLUS the extra
    chunk-batch launch (``LAUNCH_OVERHEAD_S``)."""
    if decode_backend == "fused":
        comp_dec = ragged_blocks(decode_lengths, spec.block_s)
        t = spec.num_kv_heads * comp_dec * block_time_s(spec)
        for chunk, ctx in chunks:
            t += prefill_chunk_attn_time_s(int(chunk), int(ctx), spec)
        return t
    if decode_backend == "flat":
        t = decode_attn_time_flat_s(decode_lengths, spec)
    else:
        t = decode_attn_time_s(decode_lengths, spec,
                               ragged=(decode_backend == "ragged"))
    for chunk, ctx in chunks:
        t += prefill_chunk_attn_time_s(int(chunk), int(ctx), spec)
    if len(chunks):
        ck = sum(prefill_chunk_blocks(int(c), int(x), spec.block_s)
                 for c, x in chunks)
        skipped_ck = pow2_bucket(ck) - ck
        t += (spec.num_kv_heads * skipped_ck * SKIP_OVERHEAD_S
              + LAUNCH_OVERHEAD_S)  # the separate chunk-batch launch
    return t


def allreduce_time_s(payload_bytes: float, num_devices: int) -> float:
    """Ring all-reduce wall time over ``num_devices`` chips on the ICI:
    each chip moves ``2·(n-1)/n`` of the payload through one link
    (reduce-scatter + all-gather). n <= 1 is free — the tensor-parallel
    cost terms call this unconditionally (DESIGN.md §Sharded serving)."""
    n = int(num_devices)
    if n <= 1 or payload_bytes <= 0:
        return 0.0
    return 2.0 * (n - 1) / n * float(payload_bytes) / ICI_BW


def heterogeneity_tax(lengths: Sequence[int], spec: AttnSpec) -> float:
    """Fraction of padded-backend time wasted vs. a length-homogeneous
    batch with the same total token count (the paper's Fig.-2 metric)."""
    if not len(lengths):
        return 0.0
    hetero = decode_attn_time_s(lengths, spec, ragged=False)
    mean = sum(lengths) / len(lengths)
    homog = decode_attn_time_s([mean] * len(lengths), spec, ragged=False)
    return hetero / max(homog, 1e-12)
