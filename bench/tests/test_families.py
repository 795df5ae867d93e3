"""The model family interface on the CPU.  The dense family
(``bench/families/dense_gqa.py``) gives, for the same seed, exactly what
the harness computed before its dense code moved there: the same
program configuration, operation counts, weights bit for bit and
reference logits bit for bit.  The constants below were computed by the
harness as it stood before that move."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from conftest import BENCH, TINY, with_family

SEED = 2 ** 31 + 5                     # more than 32 signed bits
MODEL = {
    "phi3-mini-3.8b": dict(
        name="phi3", num_layers=32, d_model=3072, num_heads=32,
        num_kv_heads=32, head_dim=96, d_ff=8192, vocab_size=32064,
        qkv_bias=False, rope_theta=10000.0),
    "qwen2.5-32b-l8": dict(
        name="qwen2", num_layers=8, d_model=5120, num_heads=40,
        num_kv_heads=8, head_dim=128, d_ff=27648, vocab_size=152064,
        qkv_bias=True, rope_theta=1000000.0),
}
# param_count, layer_matmul_params, kv_bytes_per_token,
# decode_attn_call(ctx [1000, 17, 1536]),
# prefill_attn_call(rows [(0, 512), (2048, 256), (5, 0)]),
# step_flops(decode [1000, 17], prefill [(2048, 256)])
COUNTS = {
    "phi3-mini-3.8b": (3821079552, 113246208, 393216,
                       (31371264, 31408128), (8460435456, 44040192),
                       2090005954560),
    "qwen2.5-32b-l8": (5457982464, 487587840, 32768,
                       (52285440, 10518528), (14100725760, 27262976),
                       2108889661440),
}
WEIGHTS = {
    "layer0": "4a75b4dc734817bdda0914d416fc784f5eb65b4fa1c5e619c967985bda5c69f8",
    "layer1.q": "e6a699fd46aa0caabe9811299f3e29a8bcaf46fa7825bddfe9384df66692b293",
    "embed": "d8d183ef53c9b5d03a901e4c947462e966d7662a05313e031d983d3781f2b9bb",
    "lm_head": "bb6c78841231057e87c8febab60fe807977ad3df6fc2c8a5bd328528cef8cb9d",
    "program tree": "0673a7aa66dee18e1e336f92809014a0bf2b2c18ef157a24d8bba7422bb1fdb5",
}
LOGITS = {
    "ref": "8bc77e954240b7c49f6c6a6f94f8e19382bf927b28e913a091b48fa12dd0a4c8",
    "int8": "be1dd8bccb66fcf3458769c2891328bcf9b10661e1380939756d1569de8ddea3",
}


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return with_family(json.load(f))


def _digest(x):
    """sha256 of an array's bytes, or of a dict's names and arrays in
    name order."""
    h = hashlib.sha256()
    for name in sorted(x) if isinstance(x, dict) else [None]:
        if name is not None:
            h.update(name.encode())
        h.update(np.asarray(x if name is None else x[name]).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(MODEL))
def test_program_config_unchanged(name):
    import system
    want = system.ModelConfig(
        family="dense", block_pattern=("attn",), norm_type="rmsnorm",
        norm_eps=1e-05, mlp_type="swiglu", tie_embeddings=False,
        dtype="bfloat16", param_dtype="bfloat16", **MODEL[name])
    assert system.model_config(_config(name)) == want


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_unchanged(name):
    import flops
    cfg = _config(name)
    assert (flops.param_count(cfg), flops.layer_matmul_params(cfg),
            flops.kv_bytes_per_token(cfg),
            flops.decode_attn_call(cfg, [1000, 17, 1536]),
            flops.prefill_attn_call(cfg, [(0, 512), (2048, 256), (5, 0)]),
            flops.step_flops(cfg, [1000, 17], [(2048, 256)])
            ) == COUNTS[name]


def _weights(what):
    import system
    import weights as W
    key = W.base_key(SEED)
    if what == "layer0":
        return W.layer(TINY, key, 0)
    if what == "layer1.q":
        return W.layer(TINY, key, 1)["q"]
    if what == "embed":
        return W.embed(TINY, key)
    if what == "lm_head":
        return W.lm_head(TINY, key)
    model = system.build_model(system.model_config(TINY),
                               system.ParallelConfig())
    params = system.make_params(model, TINY, SEED)
    return {jax.tree_util.keystr(p): a
            for p, a in jax.tree_util.tree_leaves_with_path(params)}


@pytest.mark.parametrize("what", sorted(WEIGHTS))
def test_weights_unchanged(what):
    assert _digest(_weights(what)) == WEIGHTS[what]


@pytest.fixture(scope="module")
def logits():
    from reference import Reference
    toks = np.random.default_rng(0).integers(0, TINY["vocab_size"], 40)
    return Reference(TINY, SEED, max_len=64, control="int8").logits(
        [(toks, 10, 30)])[0]


@pytest.mark.parametrize("stream", sorted(LOGITS))
def test_reference_logits_unchanged(logits, stream):
    rows = logits[stream]
    assert rows.shape == (30, 512) and rows.dtype == np.float32
    assert _digest(np.ascontiguousarray(rows)) == LOGITS[stream]


def test_leaf_ids_frozen_and_new_names_hashed():
    import weights as W
    assert [W.leaf_id(n) for n in (
        "embed", "lm_head", "final_norm", "q", "k", "v", "o", "gate", "up",
        "down", "q_bias", "k_bias", "v_bias", "input_norm", "post_norm")
            ] == list(range(15))
    new = [W.leaf_id(n) for n in ("router", "experts_up", "q_norm")]
    assert new == [1171452453, 1014908136, 2106010977]
    assert all(15 <= i < 2 ** 31 for i in new)


# layer 0 dense; every later layer a tensor ``skip_gate`` of its own (a
# name with no frozen id) and a block that passes its input on, which it
# can tell only from the layer index it is given; logits doubled by the
# family's own head
TWO_KINDS = '''
_dense_shapes, _dense_block = layer_shapes, block


def layer_shapes(cfg, i):
    extra = {"skip_gate": (cfg["hidden_size"],)} if i > 0 else {}
    return dict(_dense_shapes(cfg, i), **extra)


def block(cfg, w, x, n_valid, i):
    if "skip_gate" in w:
        return jnp.where(i > 0, x, jnp.nan)
    return _dense_block(cfg, w, x, n_valid, i)


def head(cfg, x, norm, w):
    return 2.0 * R.default_head(cfg, x, norm, w)
'''


def test_layers_of_two_kinds_and_the_family_head(tmp_path):
    """The harness hands each layer its own tensors and index and takes
    the head from the family: a two-layer model whose second layer
    passes its input on reads, through the reference, twice the logits
    of the first layer alone."""
    import weights as W
    from reference import Reference
    with open(os.path.join(BENCH, "families", "dense_gqa.py")) as f:
        dense = f.read()
    path = tmp_path / "two_kinds.py"
    path.write_text(dense + TWO_KINDS)
    cfg = dict(TINY, family_file=str(path))
    key = W.base_key(SEED)
    assert "skip_gate" not in W.layer(cfg, key, 0)
    assert W.layers(cfg, key, 1, 2)["skip_gate"].shape == (1, 64)
    with pytest.raises(ValueError, match="differ in their tensors"):
        W.layers(cfg, key, 0, 2)
    toks = np.random.default_rng(0).integers(0, TINY["vocab_size"], 40)
    two = Reference(cfg, SEED, max_len=64).logits([(toks, 10, 30)])[0]
    one = Reference(dict(TINY, num_hidden_layers=1), SEED, max_len=64
                    ).logits([(toks, 10, 30)])[0]
    np.testing.assert_array_equal(two["ref"], 2.0 * one["ref"])


def test_tied_head_is_the_embedding():
    import weights as W
    cfg = dict(TINY, tie_word_embeddings=True)
    key = W.base_key(SEED)
    np.testing.assert_array_equal(W.lm_head(cfg, key),
                                  W.embed(cfg, key).T)
