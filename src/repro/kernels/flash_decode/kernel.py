"""Flash-decode kernel: one new token against a long KV cache.

Applies the paper's level-1 tiling to the decode phase: the KV cache is
streamed through VMEM in ``block_kv`` macro-blocks (double-buffered by the
Pallas pipeline) and reduced with online softmax.  GQA query heads of one
KV group are folded into the sub-lane dimension so the per-block matmul is
(G x D) @ (D x block_kv) -- MXU-shaped instead of vector-shaped.

Per-sequence cache lengths arrive via scalar prefetch; the KV index map
clamps out-of-range blocks onto the last valid block so they are neither
fetched nor computed (grid-level tiling-mask skip, T2 at decode time).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            window: Optional[int], softcap: Optional[float], scale: float,
            block_kv: int, n_kv: int, g_pad: int, pages_t: bool = False):
    # pages_t: K/V blocks arrive as (D, block_kv), the transpose of each
    # page (see ``pages_transposed``); only the contracted dims change
    b = pl.program_id(0)
    ki = pl.program_id(2)

    kv_len = len_ref[b]
    last_valid = jnp.maximum(kv_len - 1, 0) // block_kv
    first_valid = 0
    if window is not None:
        first_valid = jnp.maximum(kv_len - window, 0) // block_kv

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((ki >= first_valid) & (ki <= last_valid))
    def _compute():
        q = q_ref[0, 0]                                   # (g_pad, D)
        k = k_ref[0, 0]                       # (block_kv, D) or (D, block_kv)
        s = jax.lax.dot_general(
            q, k, (((1,), (0 if pages_t else 1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (g_pad, block_kv)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (g_pad, block_kv), 1)
        valid = pos < kv_len
        if window is not None:
            valid = valid & (pos >= kv_len - window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.broadcast_to(jnp.max(s, axis=1, keepdims=True),
                                 m_prev.shape)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (1 if pages_t else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "scale", "block_kv", "interpret"))
def flash_decode_fwd(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     scale: Optional[float] = None,
                     block_kv: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hq, D); caches: (B, Hkv, S, D); kv_len: (B,) int32.

    Returns (B, Hq, D).
    """
    b, hq, d = q.shape
    hkv, skv = k_cache.shape[1], k_cache.shape[2]
    assert hq % hkv == 0
    g = hq // hkv
    g_pad = max(8, g)
    scale = scale if scale is not None else d ** -0.5

    block_kv = min(block_kv, skv)
    skv_p = (skv + block_kv - 1) // block_kv * block_kv
    if skv_p != skv:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    n_kv = skv_p // block_kv

    # fold GQA groups: (B, Hq, D) -> (B, Hkv, g_pad, D)
    qg = q.reshape(b, hkv, g, d)
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))

    def q_map(bi, hi, ki, len_ref):
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, ki, len_ref):
        last = jnp.maximum(len_ref[bi] - 1, 0) // block_kv
        ki = jnp.minimum(ki, last)
        if window is not None:
            first = jnp.maximum(len_ref[bi] - window, 0) // block_kv
            ki = jnp.maximum(ki, first)
        return (bi, hi, ki, 0)

    kernel = functools.partial(
        _kernel, window=window, softcap=softcap, scale=scale,
        block_kv=block_kv, n_kv=n_kv, g_pad=g_pad)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, g_pad, d), q_map),
                pl.BlockSpec((1, 1, block_kv, d), kv_map),
                pl.BlockSpec((1, 1, block_kv, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, g_pad, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((g_pad, d), jnp.float32),
                pltpu.VMEM((g_pad, LANES), jnp.float32),
                pltpu.VMEM((g_pad, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qg, k_cache, v_cache)
    return out[:, :, :g].reshape(b, hq, d)


# ---------------------------------------------------------------------------
# Paged variant: KV lives in a global page pool, indexed per sequence
# ---------------------------------------------------------------------------

def _paged_kernel(pt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, **kw):
    # Page and layer indirection happen entirely in the BlockSpec index
    # map; once a page is resident in VMEM the reduction is identical to
    # the dense case.
    del pt_ref, layer_ref
    _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, **kw)


def pages_transposed(page_size: int, d: int) -> bool:
    """Whether a TPU stores a pool's pages transposed.  Its default tiled
    layout puts a 128-multiple page-row dim minor when the head dim is
    not a multiple of the 128 lanes (a v5e lays bf16[..., 128, 96] out
    as [..., 96, 128], unpadded).  A (page_size, D) kernel block would
    then make XLA relayout the whole pool around the kernel; reading each
    page as its (D, page_size) transpose reads the pool's own bytes."""
    return d % LANES != 0 and page_size % LANES == 0


def kernel_pools(k_pages: jax.Array, v_pages: jax.Array, layer):
    """The paged kernels' view of a pool pair: a one-layer (Hkv, P,
    page_size, D) pair is lifted to the stacked (L, Hkv, P, page_size, D)
    form at layer 0 (a stacked pair keeps its ``layer``), and the last
    two dims are swapped where ``pages_transposed``.  Both are bitcasts
    of the pool's buffer.  Returns (k, v, layer as a (1,) int32,
    transposed)."""
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    pages_t = pages_transposed(*k_pages.shape[-2:])
    if pages_t:
        k_pages = jnp.swapaxes(k_pages, -1, -2)
        v_pages = jnp.swapaxes(v_pages, -1, -2)
    return (k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1),
            pages_t)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "interpret"))
def paged_flash_decode_fwd(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           kv_len: jax.Array, layer=0, *,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """Flash decode over a paged KV cache.

    q: (B, Hq, D); pages: (Hkv, P, page_size, D) global pools shared by
    every sequence, or a layer-stacked (L, Hkv, P, page_size, D) pair
    read at ``layer`` (a traced int32 scalar: the stacked pool is read in
    place, no layer is sliced out); page_table: (B, n_kv) int32 mapping
    logical KV block ``ki`` of sequence ``b`` to its physical page;
    kv_len: (B,) int32.

    The page size doubles as the kernel's ``block_kv``: the KV BlockSpec
    index map resolves the logical block through the scalar-prefetched
    page table, so the Pallas pipeline DMAs exactly the pages a sequence
    owns (clamped to the valid [first, last] logical range -- out-of-range
    grid steps re-fetch an owned page and are masked out, never touching
    pages of other sequences).  Returns (B, Hq, D).
    """
    k_pages, v_pages, layer, pages_t = kernel_pools(k_pages, v_pages, layer)
    b, hq, d = q.shape
    hkv = k_pages.shape[1]
    block_kv = k_pages.shape[3 + pages_t]
    kv_block = (None, 1, 1) + ((d, block_kv) if pages_t else (block_kv, d))
    n_kv = page_table.shape[1]
    assert hq % hkv == 0
    g = hq // hkv
    g_pad = max(8, g)
    scale = scale if scale is not None else d ** -0.5

    qg = q.reshape(b, hkv, g, d)
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))

    def q_map(bi, hi, ki, pt_ref, len_ref, layer_ref):
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, ki, pt_ref, len_ref, layer_ref):
        last = jnp.maximum(len_ref[bi] - 1, 0) // block_kv
        ki = jnp.minimum(ki, last)
        if window is not None:
            first = jnp.maximum(len_ref[bi] - window, 0) // block_kv
            ki = jnp.maximum(ki, first)
        return (layer_ref[0], hi, pt_ref[bi, ki], 0, 0)

    kernel = functools.partial(
        _paged_kernel, window=window, softcap=softcap, scale=scale,
        block_kv=block_kv, n_kv=n_kv, g_pad=g_pad, pages_t=pages_t)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hkv, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, g_pad, d), q_map),
                # one page of one head of one layer; the layer dim is
                # squeezed out of the kernel's view
                pl.BlockSpec(kv_block, kv_map),
                pl.BlockSpec(kv_block, kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, g_pad, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((g_pad, d), jnp.float32),
                pltpu.VMEM((g_pad, LANES), jnp.float32),
                pltpu.VMEM((g_pad, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32), layer,
      qg, k_pages, v_pages)
    return out[:, :, :g].reshape(b, hq, d)
