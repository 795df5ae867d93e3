"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Interpret mode cannot see what Mosaic refuses (unaligned VMEM slices,
unsupported primitives, VMEM overuse).  These tests compile each kernel
of the serving path -- and the dense two-level kernel -- at real model
widths for a ``v5e:2x2`` topology described without a chip, and assert
that the compiled program holds the kernel as a ``tpu_custom_call``.
Nothing runs, so they say nothing about results or speed.

The in-place guard compiles the paged decode and prefill step programs
at phi3-mini widths (4 stacked layers) with the pools donated, and
asserts that no buffer the size of the pool, or of one layer of it, is
copied or held as a temporary: the layer scan carries the stacked pool
and the kernels read a layer of it by index.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and a call at collection
time would break multi-worker test runs.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import ParallelConfig, get_model_config
from repro.kernels.fastattn.kernel import fastattn_fwd, paged_prefill_fwd
from repro.kernels.flash_decode.kernel import paged_flash_decode_fwd
from repro.models import build_model

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
    return text


# an HLO instruction: its name, the shapes of its result, its opcode
_INSTR = re.compile(r"^\s*(?:ROOT )?%(?P<name>\S+) = (?P<type>.*?)[)}] "
                    r"(?P<op>[a-z][\w-]*)\(")
_MOVES = re.compile(r"copy|dynamic-slice|dynamic-update-slice|transpose")


def pool_moves(text, *shapes):
    """Instructions of a compiled program that copy, slice, update by
    slice or transpose a buffer of one of ``shapes``: as an opcode, or
    in the name of a fusion."""
    dims = {"bf16[%s]" % ",".join(map(str, s)) for s in shapes}
    found = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or not any(d in m["type"] for d in dims):
            continue
        if _MOVES.fullmatch(m["op"].replace("-start", "")) or (
                m["op"] == "fusion" and _MOVES.search(m["name"])):
            found.append(line.strip()[:160])
    return found


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


STACK = 4


@pytest.mark.parametrize("name,hq,hkv,d,window,softcap", WIDTHS)
def test_stacked_paged_decode_compiles(one_chip, name, hq, hkv, d, window,
                                       softcap):
    fn = functools.partial(paged_flash_decode_fwd, window=window,
                           softcap=softcap)
    pool = (STACK, hkv, N_PAGES, PAGE, d)
    text = _assert_kernel(
        fn, _spec(one_chip, (BATCH, hq, d)), _spec(one_chip, pool),
        _spec(one_chip, pool),
        _spec(one_chip, (BATCH, PAGES_PER_SEQ), jnp.int32),
        _spec(one_chip, (BATCH,), jnp.int32),
        _spec(one_chip, (), jnp.int32))
    assert not pool_moves(text, pool, pool[1:])


@pytest.mark.parametrize("name,hq,hkv,d,window,softcap", WIDTHS)
def test_stacked_paged_prefill_compiles(one_chip, name, hq, hkv, d, window,
                                        softcap):
    fn = functools.partial(paged_prefill_fwd, window=window,
                           softcap=softcap)
    pool = (STACK, hkv, N_PAGES, PAGE, d)
    text = _assert_kernel(
        fn, _spec(one_chip, (1, hq, 512, d)), _spec(one_chip, pool),
        _spec(one_chip, pool),
        _spec(one_chip, (1, PAGES_PER_SEQ), jnp.int32),
        _spec(one_chip, (1,), jnp.int32), _spec(one_chip, (1,), jnp.int32),
        _spec(one_chip, (), jnp.int32))
    assert not pool_moves(text, pool, pool[1:])


@pytest.mark.parametrize("program", ["decode", "prefill512"])
def test_paged_step_programs_work_in_place(one_chip, program):
    """The decode and prefill programs at phi3-mini widths, 4 stacked
    layers, 80 pages of 128, pools donated: temporaries stay under one
    layer's K+V pools, and no pool- or layer-sized buffer is copied."""
    cfg = dataclasses.replace(get_model_config("phi3-mini-3.8b"),
                              num_layers=STACK)
    model = build_model(cfg, ParallelConfig(remat="none"))

    def specs(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            tree)

    params = specs(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pools = specs(jax.eval_shape(lambda: model.init_paged_cache(80, PAGE)))
    pool = jax.tree.leaves(pools)[0].shape
    layer_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(pools)) // STACK
    b = 1 if program == "prefill512" else BATCH
    table = _spec(one_chip, (b, 16), jnp.int32)
    vec = _spec(one_chip, (b,), jnp.int32)
    if program == "decode":
        fn = functools.partial(model.decode_step_paged, impl="paged")
        args = (params, vec, pools, table, vec)
    else:
        fn = functools.partial(model.prefill_chunk_paged, impl="paged")
        args = (params, _spec(one_chip, (1, 512), jnp.int32), pools, table,
                vec, vec)
    compiled = jax.jit(fn, donate_argnums=2).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes, (temp, layer_bytes)
    assert not pool_moves(text, pool, pool[1:])
