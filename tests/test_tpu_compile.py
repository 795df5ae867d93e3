"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Interpret mode cannot see what Mosaic refuses (unaligned VMEM slices,
unsupported primitives, VMEM overuse).  These tests compile each kernel
of the serving path -- and the dense two-level kernel -- at real model
widths for a ``v5e:2x2`` topology described without a chip, and assert
that the compiled program holds the kernel as a ``tpu_custom_call``.
Nothing runs, so they say nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and a call at collection
time would break multi-worker test runs.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fastattn.kernel import fastattn_fwd, paged_prefill_fwd
from repro.kernels.flash_decode.kernel import paged_flash_decode_fwd

PAGE = 128
N_PAGES = 65
PAGES_PER_SEQ = 8
BATCH = 8

# (name, Hq, Hkv, head_dim, window, softcap): phi3-mini-3.8b (MHA) and
# gemma2-2b (GQA, local window + attention logit softcap)
WIDTHS = [
    ("phi3-mini", 32, 32, 96, None, None),
    ("gemma2-2b", 8, 4, 256, 4096, 50.0),
]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name,hq,hkv,d,window,softcap", WIDTHS)
def test_paged_decode_compiles(one_chip, name, hq, hkv, d, window,
                               softcap):
    fn = functools.partial(paged_flash_decode_fwd, window=window,
                           softcap=softcap)
    _assert_kernel(
        fn, _spec(one_chip, (BATCH, hq, d)),
        _spec(one_chip, (hkv, N_PAGES, PAGE, d)),
        _spec(one_chip, (hkv, N_PAGES, PAGE, d)),
        _spec(one_chip, (BATCH, PAGES_PER_SEQ), jnp.int32),
        _spec(one_chip, (BATCH,), jnp.int32))


@pytest.mark.parametrize("batch,chunk", [(1, 512), (BATCH, 5)],
                         ids=["chunk512", "verify5"])
@pytest.mark.parametrize("name,hq,hkv,d,window,softcap", WIDTHS)
def test_paged_prefill_compiles(one_chip, name, hq, hkv, d, window,
                                softcap, batch, chunk):
    fn = functools.partial(paged_prefill_fwd, window=window,
                           softcap=softcap)
    _assert_kernel(
        fn, _spec(one_chip, (batch, hq, chunk, d)),
        _spec(one_chip, (hkv, N_PAGES, PAGE, d)),
        _spec(one_chip, (hkv, N_PAGES, PAGE, d)),
        _spec(one_chip, (batch, PAGES_PER_SEQ), jnp.int32),
        _spec(one_chip, (batch,), jnp.int32),
        _spec(one_chip, (batch,), jnp.int32))


@pytest.mark.parametrize("name,hq,hkv,d,window,softcap", WIDTHS)
def test_dense_fastattn_compiles(one_chip, name, hq, hkv, d, window,
                                 softcap):
    # 1000 rows: the padded tail exercises the kv_valid mask
    fn = functools.partial(fastattn_fwd, causal=True, window=window,
                           softcap=softcap)
    _assert_kernel(
        fn, _spec(one_chip, (1, hq, 1000, d)),
        _spec(one_chip, (1, hkv, 1000, d)),
        _spec(one_chip, (1, hkv, 1000, d)))
