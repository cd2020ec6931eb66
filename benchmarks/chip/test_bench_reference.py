"""The float32 reference against the program's own forward at a tiny
size, on weights the benchmark makes from a seed; the reference and the
weights as they were before they moved into ``arch/dense_gqa.py``; and
the weights made in their tensor-parallel shardings."""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

import harness  # noqa: E402

arch = harness.load_architecture("dense_gqa")
Reference = arch.Reference


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
    m = arch.dims(_cfg(bias, tie))
    pcfg = arch.program_config("tiny", m, 512)
    params = arch.make_params(m, seed=2**31 + 5)
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


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a, np.float32).tobytes())
    return h.hexdigest()


def _served_cfg(bias, tie):
    return dict(_cfg(bias, tie), serve={"dtype": "bfloat16"})


# sha256 of the float32 logits [50, 128] at positions 250.. of the
# sequence below, as the reference gave them before it moved into the
# architecture module (bf16-served weights, seed 2**31 + 5, qblock 64)
PINNED_LOGITS = {
    (True, False, False):
        "530e87437f63fede34962edd669f3ca723911d1545574f34b895b2adff3cad13",
    (True, False, True):
        "553c640435594a94a5ea86a7235cad072a64b10c763c661d1537e148b107fd39",
    (False, True, False):
        "f1e4cc0bfb46127a9eaa4425fbd2abe24a34146d60e1bdd6aad6f215d5eb338a",
    (False, True, True):
        "585640a42e1b5f56ee1860185338245859a5152c2ff9ce5e83f339949f4855db",
}


@pytest.mark.parametrize("bias,tie,fp8", sorted(PINNED_LOGITS),
                         ids=lambda v: str(v))
def test_reference_logits_as_before_the_move(bias, tie, fp8):
    m = arch.dims(_served_cfg(bias, tie))
    toks = np.random.default_rng(0).integers(0, 128, 300).astype(np.int32)
    lg = Reference(m, seed=2**31 + 5, qblock=64).logits(toks, 250, fp8=fp8)
    assert lg.shape == (50, 128) and lg.dtype == np.float32
    assert _digest([lg]) == PINNED_LOGITS[(bias, tie, fp8)]


def test_weights_as_before_the_move():
    m = arch.dims(_served_cfg(True, False))
    leaves = jax.tree.leaves(arch.make_params(m, seed=2**31 + 5))
    assert _digest(leaves) == \
        "3ae0b5a3f2dcbca1d139d7407dfed6ec1b9e18d8c49cb9d52c738cd4b813154b"


def test_weights_made_in_their_shardings_equal_one_device():
    """At tp = 2 each device makes only its share, in one jitted call
    whose out_shardings are the engine's serving shardings; the values
    are the one-device make's, leaf for leaf."""
    from repro.launch.shardings import serving_param_spec_tree
    m = arch.dims(dict(_served_cfg(True, False), num_hidden_layers=2))
    whole = arch.make_params(m, seed=2**40 + 3)
    shard = harness.make_params(arch, m, 2**40 + 3, tp=2)
    specs = serving_param_spec_tree(whole, 2)
    flat_w, tree_w = jax.tree.flatten(whole)
    flat_s, tree_s = jax.tree.flatten(shard)
    assert tree_w == tree_s
    sharded = 0
    for w, sh, spec in zip(flat_w, flat_s, jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))):
        assert sh.sharding.spec == spec
        assert len(sh.sharding.device_set) == 2
        if any(ax is not None for ax in spec):
            sharded += 1
            # each device holds its share, no more
            assert all(s.data.size * 2 == w.size
                       for s in sh.addressable_shards)
        np.testing.assert_array_equal(np.asarray(sh), np.asarray(w))
    assert sharded >= 8
