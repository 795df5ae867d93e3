"""FastAttention kernel vs pure-jnp oracle: shape/dtype/feature sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fastattn.kernel import fastattn_fwd
from repro.kernels.fastattn.ops import fastattn
from repro.kernels.fastattn.ref import flash_reference, standard_attention

CASES = [
    # (B, Hq, Hkv, Sq, Skv, D, causal, window, softcap, bq, bkv1, bkv2)
    (2, 4, 2, 384, 384, 64, True, None, None, 128, 256, 128),
    (1, 2, 2, 512, 512, 64, True, 100, None, 128, 256, 128),
    (1, 2, 1, 256, 256, 64, True, None, 30.0, 128, 256, 128),
    (1, 3, 1, 300, 200, 64, True, None, None, 128, 256, 128),
    (1, 2, 2, 256, 384, 64, False, None, None, 128, 256, 128),
    (1, 2, 1, 512, 512, 32, True, 200, 50.0, 128, 512, 128),
    (1, 1, 1, 64, 64, 16, True, None, None, 128, 256, 128),
    (2, 8, 2, 256, 256, 128, True, None, None, 256, 256, 256),
]


@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_standard_attention(case):
    b, hq, hkv, sq, skv, d, causal, window, softcap, bq, bkv1, bkv2 = case
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.normal(size=(b, hq, sq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), jnp.float32)
    ref = standard_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap)
    out = fastattn_fwd(q, k, v, causal=causal, window=window,
                       softcap=softcap, block_q=bq, block_kv1=bkv1,
                       block_kv2=bkv2, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_kernel_dtypes(dtype, tol):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), dtype)
    ref = standard_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32))
    out = fastattn_fwd(q, k, v, block_q=128, block_kv1=256, block_kv2=128,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.05, atol=tol * 10)


def test_q_offset_chunked_prefill_equivalence():
    """Chunked prefill with q_offset must equal one-shot prefill."""
    rng = np.random.default_rng(3)
    b, h, s, d = 1, 2, 512, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    full = fastattn_fwd(q, k, v, block_q=128, block_kv1=128,
                        block_kv2=128, interpret=True)
    halves = []
    for off in (0, 256):
        halves.append(fastattn_fwd(
            q[:, :, off:off + 256], k[:, :, :off + 256],
            v[:, :, :off + 256], q_offset=off, block_q=128,
            block_kv1=128, block_kv2=128, interpret=True))
    np.testing.assert_allclose(np.asarray(jnp.concatenate(halves, axis=2)),
                               np.asarray(full), rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("impl", ["interpret", "reference"])
def test_kv_valid_masks_padded_tail(impl):
    """fastattn(kv_valid=n) on zero-padded K/V == fastattn on K/V[:n]
    (a gathered paged view whose last page is partially filled)."""
    rng = np.random.default_rng(9)
    b, hq, hkv, d, n, s_pad = 1, 4, 2, 32, 147, 192
    q = jnp.asarray(rng.normal(size=(b, hq, n, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s_pad, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s_pad, d)), jnp.float32)
    exact = fastattn(q, k[:, :, :n], v[:, :, :n], True, None, None, None,
                     0, 64, 64, 64, impl)
    cut = fastattn(q, k, v, True, None, None, None, 0, 64, 64, 64, impl, n)
    np.testing.assert_allclose(np.asarray(cut), np.asarray(exact),
                               rtol=1e-4, atol=2e-5)


def _paged_fixture(seed=0, lens=(19, 33), hkv=2, hq=4, d=16, ps=8,
                   pool=16, n_kv=6):
    """Two sequences scattered across a scrambled page pool."""
    rng = np.random.default_rng(seed)
    table = np.zeros((len(lens), n_kv), np.int32)
    free = list(rng.permutation(np.arange(1, pool)))
    for b, n in enumerate(lens):
        for i in range(-(-n // ps)):
            table[b, i] = free.pop()
    k_pages = jnp.asarray(rng.normal(size=(hkv, pool, ps, d)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(hkv, pool, ps, d)), jnp.float32)
    return rng, jnp.asarray(table), k_pages, v_pages, hq


@pytest.mark.parametrize("kw", [dict(), dict(window=5), dict(softcap=10.0),
                                dict(window=7, softcap=25.0)])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_paged_prefill_matches_dense_oracle(kw, use_kernel):
    """Chunked-prefill attention through a scrambled page table (kernel in
    interpret mode + gather reference) == dense standard attention with
    the chunk's global q_offset, for ragged per-sequence offsets."""
    from repro.kernels.fastattn.ops import fastattn_paged_prefill
    from repro.kernels.flash_decode.ref import (paged_gather,
                                                paged_prefill_reference)
    lens = (19, 33)
    c = 7                                    # chunk: the last 7 tokens
    rng, table, k_pages, v_pages, hq = _paged_fixture(lens=lens)
    d = k_pages.shape[-1]
    q = jnp.asarray(rng.normal(size=(len(lens), hq, c, d)), jnp.float32)
    pos_start = jnp.asarray([n - c for n in lens], jnp.int32)
    kv_len = jnp.asarray(lens, jnp.int32)
    if use_kernel:
        out = fastattn_paged_prefill(q, k_pages, v_pages, table, pos_start,
                                     kv_len, block_q=8, interpret=True,
                                     **kw)
    else:
        out = paged_prefill_reference(q, k_pages, v_pages, table, pos_start,
                                      kv_len, **kw)
    dense_k = paged_gather(k_pages, table)
    dense_v = paged_gather(v_pages, table)
    for b, n in enumerate(lens):
        ref = standard_attention(
            q[b:b + 1], dense_k[b:b + 1, :, :n], dense_v[b:b + 1, :, :n],
            causal=True, q_offset=n - c, **kw)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                   np.asarray(ref), rtol=1e-4, atol=2e-5)


def test_paged_prefill_padded_chunk_window_stays_in_table():
    """A fixed-size chunk whose padding rows run past the page-table
    capacity must not push the windowed KV index map out of the table
    (regression: `first` was unclamped for fully-padded q blocks)."""
    from repro.kernels.fastattn.ops import fastattn_paged_prefill
    from repro.kernels.flash_decode.ref import paged_gather
    rng = np.random.default_rng(21)
    hkv, hq, d, ps, n_kv, pool = 1, 2, 16, 8, 4, 6
    table = jnp.asarray(np.arange(1, n_kv + 1, dtype=np.int32)[None])
    k_pages = jnp.asarray(rng.normal(size=(hkv, pool, ps, d)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(hkv, pool, ps, d)), jnp.float32)
    # chunk starts at 28 with 4 valid rows: kv_len == table capacity (32),
    # but the 12-row chunk pads to 2 q blocks of 8 -- the second block's
    # window start lands past the last table entry
    pos_start = jnp.asarray([28], jnp.int32)
    n_valid, sq = 4, 12
    kv_len = pos_start + n_valid
    q = jnp.asarray(rng.normal(size=(1, hq, sq, d)), jnp.float32)
    out = fastattn_paged_prefill(q, k_pages, v_pages, table, pos_start,
                                 kv_len, window=4, block_q=8,
                                 interpret=True)
    dense_k = paged_gather(k_pages, table)
    dense_v = paged_gather(v_pages, table)
    ref = standard_attention(q[:, :, :n_valid], dense_k, dense_v,
                             causal=True, window=4, q_offset=28)
    np.testing.assert_allclose(np.asarray(out[:, :, :n_valid]),
                               np.asarray(ref), rtol=1e-4, atol=2e-5)


def test_flash_reference_dynamic_q_offset_matches_static():
    """Traced per-batch q offsets (the chunked-prefill path) must equal
    the static-int q_offset path."""
    from repro.kernels.fastattn.ref import flash_reference_with_lse
    rng = np.random.default_rng(13)
    b, h, c, s, d = 2, 2, 5, 40, 16
    q = jnp.asarray(rng.normal(size=(b, h, c, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    off = 23
    stat, _ = flash_reference_with_lse(q, k, v, q_offset=off, block_kv=16)
    dyn, _ = jax.jit(lambda q, k, v, o: flash_reference_with_lse(
        q, k, v, q_offset=o, block_kv=16))(
            q, k, v, jnp.full((b,), off, jnp.int32))
    np.testing.assert_allclose(np.asarray(dyn), np.asarray(stat),
                               rtol=1e-5, atol=1e-6)


def test_flash_reference_matches_standard():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 4, 200, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 300, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 300, 32)), jnp.float32)
    for kw in [dict(causal=True), dict(causal=False),
               dict(causal=True, window=64),
               dict(causal=True, softcap=20.0)]:
        ref = standard_attention(q, k, v, **kw)
        out = flash_reference(q, k, v, block_kv=128, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=2e-5)


def test_custom_vjp_backward_close_to_standard():
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(fastattn(q, k, v, True, None, None, None, 0,
                                128, 128, 128, "interpret") ** 2)

    def f_ref(q, k, v):
        return jnp.sum(standard_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


# (hq, hkv, d, kw): MHA and GQA, a window, a softcap.  With 128-row
# pages, d=64 pages are read transposed (pages_transposed), d=128 pages
# as they are.
STACKED_CASES = [
    (4, 4, 64, dict()),
    (8, 2, 128, dict()),
    (8, 2, 64, dict(window=150)),
    (4, 1, 128, dict(softcap=30.0)),
]


@pytest.mark.parametrize("hq,hkv,d,kw", STACKED_CASES)
def test_paged_prefill_stacked_pool_reads_its_layer(hq, hkv, d, kw):
    """A layer-stacked (L, Hkv, P, ps, D) pool read at layer l gives what
    the one-layer call on pool[l] gives, for every l (kernel in interpret
    mode, and the gather reference)."""
    from repro.kernels.fastattn.kernel import paged_prefill_fwd
    from repro.kernels.flash_decode.ref import paged_prefill_reference
    n_layers, n_pages, ps, b, c = 3, 9, 128, 2, 16
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(b, hq, c, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(n_layers, hkv, n_pages, ps, d)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(n_layers, hkv, n_pages, ps, d)),
                    jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:8]
                        .reshape(b, 4), jnp.int32)
    pos_start = jnp.asarray([250, 3], jnp.int32)
    kv_len = pos_start + jnp.asarray([c, 9], jnp.int32)
    for layer in range(n_layers):
        got = paged_prefill_fwd(q, k, v, table, pos_start, kv_len,
                                jnp.int32(layer), block_q=8,
                                interpret=True, **kw)
        want = paged_prefill_fwd(q, k[layer], v[layer], table, pos_start,
                                 kv_len, block_q=8, interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        got = paged_prefill_reference(q, k, v, table, pos_start, kv_len,
                                      jnp.int32(layer), **kw)
        want = paged_prefill_reference(q, k[layer], v[layer], table,
                                       pos_start, kv_len, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
