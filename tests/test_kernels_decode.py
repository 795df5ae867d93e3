"""flash_decode kernel vs oracle across lengths/windows/GQA, and the
paged variant vs the dense reference through scrambled page tables."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fastattention import fast_attention_decode
from repro.kernels.flash_decode.kernel import (flash_decode_fwd,
                                               paged_flash_decode_fwd)
from repro.kernels.flash_decode.ref import paged_gather
from repro.kernels.fastattn.ref import decode_reference

CASES = [
    (2, 10, 2, 1024, 64, [1000, 321], None, None),
    (2, 4, 4, 512, 64, [512, 77], None, None),
    (2, 8, 2, 1024, 64, [900, 400], 256, None),
    (1, 4, 1, 512, 32, [511], None, 30.0),
    (3, 2, 1, 64, 16, [1, 33, 64], None, None),
    (1, 16, 2, 2048, 128, [2048], 512, None),
]


@pytest.mark.parametrize("case", CASES)
def test_decode_kernel(case):
    b, hq, hkv, s, d, lens, window, softcap = case
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    kv_len = jnp.asarray(lens, jnp.int32)
    ref = decode_reference(q, k, v, kv_len, window=window,
                           softcap=softcap)[:, :, 0]
    out = flash_decode_fwd(q[:, :, 0], k, v, kv_len, window=window,
                           softcap=softcap, block_kv=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=2e-5)


def _paginate(dense, page_size, rng, table=None):
    """Scatter a dense (B, Hkv, S, D) cache into page pools behind a
    scrambled page table (pages of different sequences interleaved in the
    pool, plus unused garbage pages)."""
    b, hkv, s, d = dense.shape
    n_kv = s // page_size
    num_pages = b * n_kv + 4                      # spare pages stay garbage
    if table is None:
        table = rng.permutation(np.arange(1, num_pages))[:b * n_kv]
        table = table.reshape(b, n_kv).astype(np.int32)
    table = np.asarray(table)
    pools = rng.normal(size=(hkv, num_pages, page_size, d))  # garbage fill
    for bi in range(b):
        for ki in range(n_kv):
            pools[:, table[bi, ki]] = \
                dense[bi, :, ki * page_size:(ki + 1) * page_size]
    return jnp.asarray(pools, jnp.float32), jnp.asarray(table)


# (b, hq, hkv, s, d, lens, window, softcap) -- ragged GQA + window + softcap
PAGED_CASES = [
    (3, 8, 2, 512, 64, [500, 129, 512], None, None),
    (2, 4, 4, 256, 64, [256, 1], None, None),
    (2, 8, 2, 512, 64, [480, 200], 128, None),
    (2, 4, 1, 256, 32, [255, 77], None, 30.0),
    (2, 16, 2, 512, 128, [384, 511], 200, 25.0),
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_matches_reference(case):
    b, hq, hkv, s, d, lens, window, softcap = case
    page_size = 128
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)), jnp.float32)
    dense_k = rng.normal(size=(b, hkv, s, d))
    dense_v = rng.normal(size=(b, hkv, s, d))
    kv_len = jnp.asarray(lens, jnp.int32)
    k_pages, table = _paginate(dense_k, page_size, rng)
    v_pages, _ = _paginate(dense_v, page_size, rng, table=table)

    ref = decode_reference(q, jnp.asarray(dense_k, jnp.float32),
                           jnp.asarray(dense_v, jnp.float32), kv_len,
                           window=window, softcap=softcap)[:, :, 0]
    out = paged_flash_decode_fwd(q[:, :, 0], k_pages, v_pages, table,
                                 kv_len, window=window, softcap=softcap,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    # the jittable gather-reference path agrees too
    out_ref = fast_attention_decode(
        q.transpose(0, 2, 1, 3), k_pages, v_pages, kv_len, window=window,
        softcap=softcap, impl="paged_reference", page_table=table)
    np.testing.assert_allclose(
        np.asarray(out_ref.transpose(0, 2, 1, 3)[:, :, 0]),
        np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_paged_gather_roundtrip():
    rng = np.random.default_rng(3)
    dense = rng.normal(size=(2, 2, 256, 32))
    pages, table = _paginate(dense, 128, rng)
    got = paged_gather(pages, table)
    np.testing.assert_allclose(np.asarray(got), dense, rtol=1e-6,
                               atol=1e-6)


def test_paged_facade_matches_dense_reference_impl():
    """fast_attention_decode(impl="paged_interpret") == impl="reference"
    on the same logical cache, ragged GQA batch."""
    b, hq, hkv, s, d, page_size = 3, 8, 2, 384, 64, 128
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.float32)
    dense_k = rng.normal(size=(b, hkv, s, d))
    dense_v = rng.normal(size=(b, hkv, s, d))
    kv_len = jnp.asarray([384, 129, 17], jnp.int32)
    k_pages, table = _paginate(dense_k, page_size, rng)
    v_pages, _ = _paginate(dense_v, page_size, rng, table=table)
    ref = fast_attention_decode(
        q, jnp.asarray(dense_k.transpose(0, 2, 1, 3), jnp.float32),
        jnp.asarray(dense_v.transpose(0, 2, 1, 3), jnp.float32), kv_len,
        impl="reference")
    out = fast_attention_decode(q, k_pages, v_pages, kv_len,
                                impl="paged_interpret", page_table=table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_paged_requires_page_table():
    q = jnp.zeros((1, 1, 4, 32), jnp.float32)
    pages = jnp.zeros((2, 4, 128, 32), jnp.float32)
    with pytest.raises(ValueError, match="page_table"):
        fast_attention_decode(q, pages, pages,
                              jnp.asarray([1], jnp.int32), impl="paged")


def test_decode_block_size_invariance():
    rng = np.random.default_rng(1)
    b, hq, hkv, s, d = 2, 8, 2, 768, 64
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    kv_len = jnp.asarray([700, 123], jnp.int32)
    outs = [flash_decode_fwd(q, k, v, kv_len, block_kv=bk, interpret=True)
            for bk in (128, 256, 768)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=1e-5, atol=1e-5)


# (hq, hkv, d, window, softcap): MHA and GQA, a window, a softcap.  With
# 128-row pages, d=64 pages are read transposed (pages_transposed), d=128
# pages as they are.
STACKED_CASES = [
    (4, 4, 64, None, None),
    (8, 2, 128, None, None),
    (8, 2, 64, 200, None),
    (4, 1, 128, None, 30.0),
]


@pytest.mark.parametrize("case", STACKED_CASES)
def test_paged_decode_stacked_pool_reads_its_layer(case):
    """A layer-stacked (L, Hkv, P, ps, D) pool read at layer l gives what
    the one-layer call on pool[l] gives, for every l (kernel in interpret
    mode, and the gather reference)."""
    from repro.kernels.flash_decode.ref import paged_decode_reference
    hq, hkv, d, window, softcap = case
    n_layers, n_pages, ps, b = 3, 9, 128, 2
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(n_layers, hkv, n_pages, ps, d)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(n_layers, hkv, n_pages, ps, d)),
                    jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:8]
                        .reshape(b, 4), jnp.int32)
    kv_len = jnp.asarray([500, 77], jnp.int32)
    kw = dict(window=window, softcap=softcap)
    for layer in range(n_layers):
        got = paged_flash_decode_fwd(q, k, v, table, kv_len,
                                     jnp.int32(layer), interpret=True, **kw)
        want = paged_flash_decode_fwd(q, k[layer], v[layer], table, kv_len,
                                      interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        got = paged_decode_reference(q[:, :, None], k, v, table, kv_len,
                                     jnp.int32(layer), **kw)
        want = paged_decode_reference(q[:, :, None], k[layer], v[layer],
                                      table, kv_len, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
