"""The plain reference against the program's own float32 forward, on the
benchmark's weights at a tiny size: the same architecture (rotary
halves, grouped KV heads, QKV bias, RMSNorm, SwiGLU), with weights laid
into the program's tree by ``system.make_params``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY


def test_reference_matches_program_forward():
    import system
    from reference import Reference
    cfg = dict(TINY, num_hidden_layers=3)
    model = system.build_model(system.model_config(cfg),
                               system.ParallelConfig())
    seed = 2 ** 31 + 5                     # more than 32 signed bits
    params = system.make_params(model, cfg, seed)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply(f32, jnp.asarray(toks[None]),
                                      impl="reference")[0])
    got = Reference(cfg, seed, max_len=64).logits([(toks, 10, 30)])[0]["ref"]
    np.testing.assert_allclose(got, want[10:40], atol=2e-5, rtol=2e-4)


def test_control_is_int8_per_column():
    from reference import int8_roundtrip
    w = jnp.asarray(np.random.default_rng(1).normal(size=(64, 8)),
                    jnp.float32)
    q = np.asarray(int8_roundtrip(w))
    step = np.abs(np.asarray(w)).max(0) / 127
    assert np.all(np.abs(q - np.asarray(w)) <= step / 2 + 1e-7)
    assert len(np.unique(np.round(q[:, 0] / step[0]))) <= 255


def test_control_is_e4m3_per_column():
    """The float8 round trip against ml_dtypes' e4m3 on the host: the
    same values, and (unlike a cast the compiler could drop) it changes
    most elements of a matrix drawn like the served weights."""
    import ml_dtypes
    from reference import e4m3_roundtrip
    w = np.random.default_rng(2).normal(size=(256, 16)).astype(np.float32)
    w = (w * 0.02).astype(ml_dtypes.bfloat16).astype(np.float32)
    q = np.asarray(e4m3_roundtrip(jnp.asarray(w)))
    scale = np.abs(w).max(0) / 448.0
    want = (w / scale).astype(ml_dtypes.float8_e4m3fn).astype(
        np.float32) * scale
    np.testing.assert_allclose(q, want, rtol=1e-6, atol=0)
    assert np.mean(q != w) > 0.5
    # every e4m3 value, the midpoints between neighbours and beyond
    from reference import e4m3_round
    grid = np.unique(np.arange(256, dtype=np.uint8).view(
        ml_dtypes.float8_e4m3fn).astype(np.float32))
    grid = grid[np.isfinite(grid)]
    mids = (grid[1:] + grid[:-1]) / 2
    xs = np.concatenate([grid, mids, np.nextafter(mids, np.inf),
                         np.nextafter(mids, -np.inf)]).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(e4m3_round(jnp.asarray(xs))),
        xs.astype(ml_dtypes.float8_e4m3fn).astype(np.float32))


@pytest.mark.parametrize("kind,lo,hi", [("int8", 0.002, 0.03),
                                        ("e4m3", 0.01, 0.08)])
def test_roundtrip_stats(kind, lo, hi):
    """The control's own programs change most of the weights, by the
    error its precision implies (int8 per column: about 1%; e4m3: about
    3%)."""
    from reference import Reference
    cfg = dict(TINY, hidden_size=128, intermediate_size=256)
    st = Reference(cfg, 11, max_len=32, control=kind).roundtrip_stats()
    assert st["changed_share"] > 0.5, st
    assert lo < st["rel_error"] < hi, st
