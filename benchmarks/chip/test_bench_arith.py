"""The benchmark's FLOP and byte arithmetic against hand counts and
against the values it gave before it moved into ``arch/dense_gqa.py``,
the per-chip arithmetic of the readers that use it, and the peak
table."""
import importlib.util
from pathlib import Path

import pytest

import harness
import peaks

flops = harness.load_architecture("dense_gqa")

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


# Qwen2.5-14B's published widths at full depth
Q = {"d_model": 5120, "num_heads": 40, "num_kv_heads": 8, "head_dim": 128,
     "d_ff": 13824, "vocab_size": 152064, "num_layers": 48}


def test_arithmetic_as_before_the_move():
    # the values the arithmetic gave in its own module (flops.py), before
    # it moved into the architecture module: the same to the last digit
    assert [flops.token_flops(Q, 1000, True),
            flops.token_flops(Q, 7, False)] == [28964290560, 26430996480]
    assert [flops.chunk_flops(Q, 512, 256, True),
            flops.chunk_flops(Q, 0, 1984, False)] == [6927317729280,
                                                      54361168281600]
    assert repr(flops.attn_least_time(
        Q, [5, 900, 1999], [(512, 256), (0, 64)], 197e12, 819e9)) \
        == "0.0012845536117216116"


MS = 1_000_000  # ns
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, Path(__file__).parent / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _step(n, t0, t1, decode_ctx, chunks):
    return harness.EngineStep(0, n, t0, t1, decode_ctx, chunks, 48, 0.5)


def _ctx(chips):
    """Three engine steps (two in a 1 s window) and a two-chip trace of
    the kernel inside the first two."""
    import types
    import xtrace
    steps = [_step(1, 10.0, 10.2, [700, 1500, 33], [(0, 256, False)]),
             _step(2, 10.2, 10.45, [701, 1501, 34, 90], [(256, 100, True)]),
             _step(3, 11.5, 11.6, [5], [])]
    tr = xtrace.Trace([("paged_mixed_attention.3", 1 * MS, 3 * MS, 0),
                       ("paged_mixed_attention.3", 1 * MS, 3.5 * MS, 1),
                       ("paged_mixed_attention.4", 5 * MS, 9 * MS, 0),
                       ("paged_mixed_attention.4", 5 * MS, 8 * MS, 1)],
                      [("engine.step", 0, 4 * MS, {"n": 1}),
                       ("engine.step", 4 * MS, 10 * MS, {"n": 2})], 2)
    return {"flops": flops, "m": Q, "t_open": 10.0, "t_close": 11.0,
            "rec": types.SimpleNamespace(engine_steps=steps), "trace": tr,
            "peaks": PEAKS, "chips": chips}


# the readings of the readers as they were before ``chips`` (one chip's
# peak), on the context above
ONE_CHIP = {"mfu": 4.909320223187818,
            "paged_mixed_attention_roofline": 29.16607714604237}


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_reader_per_chip(name):
    read = _reader(name)
    one, four = read(_ctx(1)), read(_ctx(4))
    # one chip reads as before, to the last digit; the same work on four
    # chips in the same time is a quarter of each chip's peak
    assert one == ONE_CHIP[name]
    assert one / four == pytest.approx(4.0, rel=1e-12)


def test_peaks_known_and_unknown():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
