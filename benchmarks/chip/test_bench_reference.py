"""The float32 reference against the program's own forward at a tiny
size, on weights the benchmark makes from a seed."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

import harness  # noqa: E402
import weights  # noqa: E402
from reference import Reference  # noqa: E402


def _cfg(bias, tie):
    return {"name": "tiny", "model_type": "qwen2" if bias else "llama",
            "attention_bias": bias, "hidden_size": 64,
            "intermediate_size": 96, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_hidden_layers": 3,
            "vocab_size": 128, "max_position_embeddings": 512,
            "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
            "tie_word_embeddings": tie, "serve": {"dtype": "float32"}}


@pytest.mark.parametrize("bias,tie", [(True, False), (False, True)],
                         ids=["qkv-bias-untied", "tied-head"])
def test_reference_matches_program_forward(bias, tie):
    from repro.models import build_model
    from repro.models.transformer import forward_full
    m = harness.model_dims(_cfg(bias, tie))
    pcfg = harness.program_config("tiny", m, 512)
    params = weights.make_params(m, seed=2**31 + 5)
    # the benchmark's tree is the program's tree
    want = jax.eval_shape(build_model(pcfg).init, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert all(a.shape == b.shape for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(want)))
    toks = np.random.default_rng(0).integers(0, 128, 300).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog, _, _ = forward_full(params, pcfg, jnp.asarray(toks)[None])
    prog = np.asarray(prog[0, 250:], np.float32)
    ref = Reference(m, seed=2**31 + 5, qblock=64).logits(toks, 250)
    assert ref.shape == prog.shape
    scale = np.abs(ref).max()
    assert np.abs(ref - prog).max() <= 1e-4 * scale
    # fp8 (the control) departs visibly
    ctl = Reference(m, seed=2**31 + 5, qblock=64).logits(toks, 250, fp8=True)
    assert np.abs(ctl - ref).max() > 1e-2 * scale
