"""FastAttention forward kernel: two-level tiling on TPU (paper §4.1).

Level 1 (paper: GM -> L1, large blocks, double buffered):
    the Pallas grid streams K/V *macro-blocks* of ``block_kv1`` rows from
    HBM into VMEM; Pallas' software pipeline double-buffers these DMAs so
    transfer of macro-block n+1 overlaps compute on n.  Large level-1
    blocks amortize DMA setup and cut the number of grid synchronizations
    -- the Ascend Cube<->Vector sync the paper eliminates.

Level 2 (paper: L1 -> L0, small blocks, Cube/Vector pipelining):
    inside the kernel a ``fori_loop`` walks ``block_kv1 // block_kv2``
    MXU-aligned *sub-tiles*.  Per sub-tile the MXU computes Q @ K_sub^T
    while the VPU applies softcap/mask/online-softmax -- back-to-back ops
    the Mosaic compiler pipelines across sub-tiles (the Cube/Vector overlap
    of Figure 2).

Tiling-mask (paper §4.1, T2): sub-tiles are classified SKIP / PARTIAL /
FULL.  SKIP blocks are pruned both at the grid level (the KV index map
clamps to the last valid macro-block, so pruned blocks are neither fetched
nor computed) and at sub-tile level (pl.when).  FULL blocks skip the mask
entirely (the Vector-unit saving).  A PARTIAL sub-tile's B-mask is built
from iota comparisons of its global row/column positions -- the same mask
as the paper's shifted M-mask slice (core/tiling_mask.py, the reference
the tests check), without the unaligned VMEM slice Mosaic cannot lower.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode.kernel import kernel_pools

NEG_INF = -1e30
LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *,
            causal: bool, window: Optional[int], softcap: Optional[float],
            scale: float, q_offset: int, kv_valid: int,
            block_q: int, block_kv1: int, block_kv2: int, n_kv1: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    q_start = q_offset + qi * block_q          # global position of q row 0
    q_end = q_start + block_q - 1

    # ---- level-1 block validity (grid-level skip) -------------------------
    last_valid = n_kv1 - 1
    if causal:
        last_valid = jnp.minimum(last_valid, q_end // block_kv1)
    last_valid = jnp.minimum(last_valid, (kv_valid - 1) // block_kv1)
    first_valid = 0
    if window is not None:
        first_valid = jnp.maximum(
            0, (q_start - window + 1) // block_kv1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((ki >= first_valid) & (ki <= last_valid))
    def _compute():
        q = q_ref[0, 0]                        # (block_q, d)
        n_sub = block_kv1 // block_kv2

        def sub_tile(j, _):
            kv_start = ki * block_kv1 + j * block_kv2
            kv_end = kv_start + block_kv2 - 1
            delta = q_start - kv_start

            # ---- sub-tile classification (T2) --------------------------
            skip = jnp.bool_(False)
            full = jnp.bool_(True)
            if causal:
                skip = skip | (delta <= -block_q)
                full = full & (delta >= block_kv2 - 1)
            if window is not None:
                skip = skip | (kv_end <= q_start - window)
                full = full & (kv_start >= q_end - window + 1)
            skip = skip | (kv_start >= kv_valid)
            full = full & (kv_end < kv_valid)

            @pl.when(~skip)
            def _do():
                k_sub = k_ref[0, 0, pl.ds(j * block_kv2, block_kv2), :]
                # MXU: scores in f32
                s = jax.lax.dot_general(
                    q, k_sub, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if softcap is not None:
                    s = softcap * jnp.tanh(s / softcap)

                def _masked(s):
                    # B-mask of a PARTIAL sub-tile (VPU work): global
                    # row/column positions compared arithmetically.
                    rows = q_start + jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_kv2), 0)
                    cols = kv_start + jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_kv2), 1)
                    bm = cols < kv_valid
                    if causal:
                        bm = bm & (rows >= cols)
                    if window is not None:
                        bm = bm & (rows - cols < window)
                    return jnp.where(bm, s, NEG_INF)

                s = jax.lax.cond(full, lambda s: s, _masked, s)

                # ---- online softmax update (VPU) ------------------------
                m_prev = m_ref[...]                       # (block_q, LANES)
                m_cur = jnp.max(s, axis=1, keepdims=True)  # (block_q, 1)
                m_cur = jnp.broadcast_to(m_cur, m_prev.shape)
                m_new = jnp.maximum(m_prev, m_cur)
                alpha = jnp.exp(m_prev - m_new)            # (block_q, LANES)
                p = jnp.exp(s - m_new[:, :1])
                l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
                    jnp.sum(p, axis=1, keepdims=True), m_prev.shape)
                pv = jax.lax.dot_general(
                    p.astype(v_ref.dtype),
                    v_ref[0, 0, pl.ds(j * block_kv2, block_kv2), :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv
                m_ref[...] = m_new

            return 0

        jax.lax.fori_loop(0, n_sub, sub_tile, 0, unroll=True)

    @pl.when(ki == n_kv1 - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "q_offset",
                     "kv_valid", "block_q", "block_kv1", "block_kv2",
                     "interpret"))
def fastattn_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool = True,
                 window: Optional[int] = None,
                 softcap: Optional[float] = None,
                 scale: Optional[float] = None,
                 q_offset: int = 0,
                 kv_valid: Optional[int] = None,
                 block_q: int = 256,
                 block_kv1: int = 1024,
                 block_kv2: int = 256,
                 interpret: bool = False) -> jax.Array:
    """Two-level-tiled FlashAttention2 forward on TPU.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D), Hq % Hkv == 0.
    Sequence lengths need not be multiples of the block sizes (padded
    internally; the padded tail is masked like any KV past ``kv_valid``).
    ``kv_valid`` marks only the first rows of K/V as real (a gathered
    paged view whose last page is partially filled); the tail is masked
    exactly like internal padding.
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    n_rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    kv_valid = skv if kv_valid is None else min(kv_valid, skv)

    block_q = min(block_q, max(sq, 8))
    block_kv2 = min(block_kv2, block_kv1)
    # pad sequences to block multiples
    sq_p = (sq + block_q - 1) // block_q * block_q
    skv_p = (skv + block_kv1 - 1) // block_kv1 * block_kv1
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))

    n_q = sq_p // block_q
    n_kv1 = skv_p // block_kv1

    grid = (b, hq, n_q, n_kv1)

    def q_map(bi, hi, qi, ki):
        return (bi, hi, qi, 0)

    def kv_map(bi, hi, qi, ki):
        # Grid-level skip: clamp pruned blocks onto the nearest valid one so
        # the pipeline does not re-DMA them (consecutive identical indices
        # reuse the resident VMEM buffer).
        last = jnp.minimum(n_kv1 - 1, (kv_valid - 1) // block_kv1)
        if causal:
            q_end = q_offset + (qi + 1) * block_q - 1
            last = jnp.minimum(last, q_end // block_kv1)
        ki = jnp.minimum(ki, last)
        if window is not None:
            first = jnp.maximum(
                0, (q_offset + qi * block_q - window + 1) // block_kv1)
            ki = jnp.maximum(ki, first)
        return (bi, hi // n_rep, ki, 0)

    kernel = functools.partial(
        _kernel, causal=causal, window=window, softcap=softcap, scale=scale,
        q_offset=q_offset, kv_valid=kv_valid, block_q=block_q,
        block_kv1=block_kv1, block_kv2=block_kv2, n_kv1=n_kv1)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_kv1, d), kv_map),
            pl.BlockSpec((1, 1, block_kv1, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq, :]


# ---------------------------------------------------------------------------
# Paged chunked prefill: a block of prompt tokens against the KV page pools
# ---------------------------------------------------------------------------

def _paged_prefill_kernel(pt_ref, start_ref, len_ref, layer_ref, q_ref,
                          k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                          window: Optional[int], softcap: Optional[float],
                          scale: float, block_q: int, page_size: int,
                          n_kv: int, pages_t: bool):
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    q_start = start_ref[bi] + qi * block_q     # global position of q row 0
    kv_len = len_ref[bi]

    # ---- level-1 page validity (grid-level skip, dynamic offsets) ---------
    last_valid = jnp.minimum((q_start + block_q - 1) // page_size,
                             jnp.maximum(kv_len - 1, 0) // page_size)
    first_valid = 0
    if window is not None:
        first_valid = jnp.maximum(0, (q_start - window + 1) // page_size)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((ki >= first_valid) & (ki <= last_valid))
    def _compute():
        q = q_ref[0, 0]                        # (block_q, d)
        k = k_ref[0, 0]            # (page_size, d), or (d, page_size)
        s = jax.lax.dot_general(
            q, k, (((1,), (0 if pages_t else 1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # Chunk offsets are per-sequence runtime values, so the mask is
        # arithmetic (iota) rather than the static M-mask slice trick.
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 0)
        cols = ki * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 1)
        mask = (rows >= cols) & (cols < kv_len)
        if window is not None:
            mask = mask & (rows - cols < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                        # (block_q, LANES)
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
    static_argnames=("window", "softcap", "scale", "block_q", "interpret"))
def paged_prefill_fwd(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      page_table: jax.Array, pos_start: jax.Array,
                      kv_len: jax.Array, layer=0, *,
                      window: Optional[int] = None,
                      softcap: Optional[float] = None,
                      scale: Optional[float] = None,
                      block_q: int = 256,
                      interpret: bool = False) -> jax.Array:
    """Causal prefill of one prompt chunk against the paged KV pools.

    q: (B, Hq, Sq, D) -- the chunk's queries, already RoPE'd at their
    global positions; pages: (Hkv, P, page_size, D) global pools, or a
    layer-stacked (L, Hkv, P, page_size, D) pair read in place at
    ``layer`` (a traced int32 scalar) -- the chunk's K/V rows must
    already be written in; page_table: (B, n_kv) int32; pos_start: (B,)
    int32 global position of the chunk's first token; kv_len: (B,) int32
    valid KV length (= pos_start + valid chunk tokens).  All offsets are
    runtime values fed through scalar prefetch, so one trace serves
    every chunk of every prompt: the KV BlockSpec index map resolves
    logical block ki -> page_table[b, ki] and clamps to the
    causally-valid page range of the chunk (the grid-level tiling-mask
    skip of the dense kernel, with dynamic bounds).
    Returns (B, Hq, Sq, D).
    """
    k_pages, v_pages, layer, pages_t = kernel_pools(k_pages, v_pages, layer)
    b, hq, sq, d = q.shape
    hkv = k_pages.shape[1]
    page_size = k_pages.shape[3 + pages_t]
    kv_block = (None, 1, 1) + ((d, page_size) if pages_t
                               else (page_size, d))
    n_kv = page_table.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    n_rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5

    block_q = min(block_q, max(sq, 8))
    sq_p = (sq + block_q - 1) // block_q * block_q
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    n_q = sq_p // block_q

    def q_map(bi, hi, qi, ki, pt_ref, start_ref, len_ref, layer_ref):
        return (bi, hi, qi, 0)

    def kv_map(bi, hi, qi, ki, pt_ref, start_ref, len_ref, layer_ref):
        # clamp pruned logical blocks onto the nearest valid one so the
        # pipeline re-uses the resident page instead of DMAing a new one
        q_end = start_ref[bi] + (qi + 1) * block_q - 1
        last = jnp.minimum(q_end // page_size,
                           jnp.maximum(len_ref[bi] - 1, 0) // page_size)
        kj = jnp.minimum(ki, last)
        if window is not None:
            first = jnp.maximum(
                0, (start_ref[bi] + qi * block_q - window + 1) // page_size)
            kj = jnp.maximum(kj, first)
        # fully-padded q blocks of the last chunk can push `first` (and
        # thus kj) past the table width -- clamp so the scalar-prefetch
        # read stays in bounds (their rows are masked in the kernel)
        return (layer_ref[0], hi // n_rep,
                pt_ref[bi, jnp.minimum(kj, n_kv - 1)], 0, 0)

    kernel = functools.partial(
        _paged_prefill_kernel, window=window, softcap=softcap, scale=scale,
        block_q=block_q, page_size=page_size, n_kv=n_kv, pages_t=pages_t)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, hq, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), q_map),
                # one page of one head of one layer; the layer dim is
                # squeezed out of the kernel's view
                pl.BlockSpec(kv_block, kv_map),
                pl.BlockSpec(kv_block, kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),      # acc
                pltpu.VMEM((block_q, LANES), jnp.float32),  # running max
                pltpu.VMEM((block_q, LANES), jnp.float32),  # running sum
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos_start.astype(jnp.int32),
      kv_len.astype(jnp.int32), layer, q, k_pages, v_pages)
    return out[:, :, :sq, :]
