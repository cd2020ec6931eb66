"""The benchmark's FLOP and byte arithmetic against hand counts, and
the peak table."""
import pytest

import flops
import peaks

# a small model: d 8, 2 heads of 4, 1 kv head, ffn 16, vocab 10, 2 layers
M = {"d_model": 8, "num_heads": 2, "num_kv_heads": 1, "head_dim": 4,
     "d_ff": 16, "vocab_size": 10, "num_layers": 2}


def test_matmul_params_by_hand():
    # q 8x8 + k 8x4 + v 8x4 + o 8x8 = 192; ffn 3 x 8 x 16 = 384
    assert flops.layer_matmul_params(M) == 192 + 384
    assert flops.head_params(M) == 80


def test_token_flops_by_hand():
    # 2 x 576 x 2 layers + attention 4 x 2 x 4 x ctx 5 x 2 layers + head
    assert flops.token_flops(M, 5, True) == 2304 + 320 + 160
    assert flops.token_flops(M, 5, False) == 2304 + 320


def test_chunk_equals_its_tokens():
    # a chunk of 3 after 4 cached tokens = tokens over ctx 5, 6, 7
    per = sum(flops.token_flops(M, c, False) for c in (5, 6, 7))
    assert flops.chunk_flops(M, 4, 3, False) == per
    assert flops.chunk_flops(M, 4, 3, True) == per + 160


def test_attention_call_by_hand():
    # decode ctx 3: 4*2*4*3 = 96 FLOPs, rows 3; chunk (2, 2): keys 3+4=7
    # -> 224 FLOPs, rows 4
    f, b = flops.attn_call_cost(M, [3], [(2, 2)])
    assert f == 96 + 224
    # K and V rows: 2 x 7 rows x 1 head x 4 x 2 B; q and o: 2 x 3 rows x
    # 2 heads x 4 x 2 B
    assert b == 2 * 7 * 4 * 2 + 2 * 3 * 2 * 4 * 2
    t = flops.attn_least_time(M, [3], [(2, 2)], peak_flops=1e3,
                              hbm_bw=1e3)
    assert t == pytest.approx(2 * max(320 / 1e3, b / 1e3))


def test_peaks_known_and_unknown():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
