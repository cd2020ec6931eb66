"""Sharding rules + roofline extraction (host-scale checks; the 256/512-chip
lowering is exercised by launch/dryrun.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.roofline import (Roofline, collective_wire_bytes,
                                   model_flops, parse_collectives)
from repro.launch.shardings import param_spec, param_shardings
from repro.models import build_model


def test_param_spec_rules():
    assert param_spec("embed", (512, 64), 16) == P("model", None)
    assert param_spec("unembed", (64, 512), 16) == P(None, "model")
    assert param_spec("layers/attn/wq", (4, 64, 512), 16) == \
        P(None, None, "model")
    assert param_spec("layers/attn/wo", (4, 512, 64), 16) == \
        P(None, "model", None)
    assert param_spec("layers/moe/w_gate", (4, 128, 64, 32), 16) == \
        P(None, "model", None, None)
    assert param_spec("layers/moe/router", (4, 64, 128), 16) == P()
    assert param_spec("layers/ln_attn", (4, 64), 16) == P()


def test_param_spec_divisibility_fallback():
    # 100 not divisible by 16 -> replicate; divisible by 10 -> shard
    assert param_spec("layers/attn/wq", (2, 100, 100), 16) == P()
    assert param_spec("layers/attn/wq", (2, 100, 100), 10) == \
        P(None, None, "model")


def test_all_params_get_spec_without_error():
    mesh = make_host_mesh()
    for arch in ("smollm-360m", "qwen3-moe-30b-a3b", "rwkv6-7b",
                 "zamba2-2.7b", "whisper-large-v3"):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        shardings = param_shardings(shapes, mesh)
        assert (len(jax.tree.leaves(shardings))
                == len(jax.tree.leaves(shapes)))


def test_host_mesh_lowering_smoke():
    """End-to-end pjit lowering on the local device mesh."""
    mesh = make_host_mesh()
    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ps = param_shardings(shapes, mesh)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}

    def loss_fn(p, b):
        return model.loss(p, b)[0]

    with mesh:
        lowered = jax.jit(loss_fn, in_shardings=(ps, None)).lower(
            shapes, batch)
        compiled = lowered.compile()
        assert compiled.cost_analysis()["flops"] > 0


# ---- roofline extraction ----------------------------------------------------
FAKE_HLO = """
HloModule test
%add { ... }
  %p0 = bf16[128,256]{1,0} parameter(0)
  %dot.1 = f32[8,4096]{1,0} dot(%p0, %p0)
  %all-reduce.2 = f32[8,4096]{1,0} all-reduce(%dot.1), replica_groups=[32,16]<=[512]
  %ag.3 = bf16[64,256]{1,0} all-gather(%p0), dimensions={0}
  %rs = f32[8,256]{1,0} reduce-scatter(%all-reduce.2), dimensions={0}
  %cp = bf16[128,256]{1,0} collective-permute(%p0)
"""


def test_parse_collectives():
    recs = parse_collectives(FAKE_HLO)
    kinds = sorted(r["op"] for r in recs)
    assert kinds == ["all-gather", "all-reduce", "collective-permute",
                     "reduce-scatter"]
    ar = next(r for r in recs if r["op"] == "all-reduce")
    assert ar["operand_bytes"] == 8 * 4096 * 4          # resolved via defs
    ag = next(r for r in recs if r["op"] == "all-gather")
    assert ag["result_bytes"] == 64 * 256 * 2


def test_collective_wire_bytes_factors():
    recs = parse_collectives(FAKE_HLO)
    total = collective_wire_bytes(recs)
    expect = (2.0 * 8 * 4096 * 4            # all-reduce 2x operand
              + 64 * 256 * 2                # all-gather result
              + 8 * 4096 * 4                # reduce-scatter operand
              + 128 * 256 * 2)              # collective-permute operand
    assert total == pytest.approx(expect)


def test_roofline_terms_and_dominance():
    rl = Roofline(arch="x", shape="train_4k", mesh="16x16",
                  flops_per_chip=197e12, bytes_per_chip=0.0,
                  collective_bytes_per_chip=0.0, num_chips=256,
                  model_flops_global=197e12 * 256 / 2)
    assert rl.t_compute == pytest.approx(1.0)
    assert rl.dominant == "compute"
    assert rl.useful_flops_ratio == pytest.approx(0.5)


def test_model_flops_scaling():
    cfg = get_config("smollm-360m")
    train = model_flops(cfg, "train_4k", 4096, 256)
    dec = model_flops(cfg, "decode_32k", 32768, 128)
    assert train > dec
    assert train == pytest.approx(3 * model_flops(cfg, "prefill_32k", 4096,
                                                  256), rel=1e-6)
