"""Oracles for flash_decode: dense re-export + paged-gather reference.

``paged_gather`` materialises the dense (B, Hkv, S, D) view of a paged
pool; ``paged_decode_reference`` chains it with the dense decode oracle so
paged kernels have an f32-softmax reference on any backend.  Both take
the kernels' arguments: one-layer (Hkv, P, page_size, D) pools, or
layer-stacked (L, Hkv, P, page_size, D) pools read at ``layer``.
``paged_prefill_reference`` is the chunked-prefill analogue: gather +
online-softmax flash with runtime per-sequence query offsets (the
jittable CPU path of the paged prefill kernel).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.fastattn.ref import (decode_reference,  # noqa: F401
                                        flash_reference_with_lse)


def paged_gather(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """pages: (Hkv, P, page_size, D); page_table: (B, n_kv) int32.

    Returns the dense per-sequence view (B, Hkv, n_kv * page_size, D).
    """
    g = pages[:, page_table]                   # (Hkv, B, n_kv, ps, D)
    hkv, b, n_kv, ps, d = g.shape
    return g.transpose(1, 0, 2, 3, 4).reshape(b, hkv, n_kv * ps, d)


def _layer_pools(k_pages, v_pages, layer):
    if k_pages.ndim == 5:
        return k_pages[layer], v_pages[layer]
    return k_pages, v_pages


def paged_decode_reference(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           kv_len: jax.Array, layer=0, *,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None) -> jax.Array:
    """q: (B, Hq, 1, D) against paged pools.  Returns (B, Hq, 1, D)."""
    k_pages, v_pages = _layer_pools(k_pages, v_pages, layer)
    k = paged_gather(k_pages, page_table)
    v = paged_gather(v_pages, page_table)
    return decode_reference(q, k, v, kv_len, window=window, softcap=softcap,
                            scale=scale)


def paged_prefill_reference(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, page_table: jax.Array,
                            pos_start: jax.Array, kv_len: jax.Array,
                            layer=0, *,
                            window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            scale: Optional[float] = None,
                            block_kv: int = 512) -> jax.Array:
    """Chunked-prefill attention oracle over paged pools.

    q: (B, Hq, Sq, D) chunk queries; pos_start: (B,) int32 global position
    of each sequence's chunk start; kv_len: (B,) int32 valid KV length.
    Both offsets are runtime values, so a single trace serves every chunk
    of every prompt (the gathered view has the fixed page-table width).
    Returns (B, Hq, Sq, D); rows past the valid chunk length are garbage
    and must be ignored by the caller.
    """
    k_pages, v_pages = _layer_pools(k_pages, v_pages, layer)
    k = paged_gather(k_pages, page_table)
    v = paged_gather(v_pages, page_table)
    out, _ = flash_reference_with_lse(
        q, k, v, causal=True, window=window, softcap=softcap, scale=scale,
        q_offset=pos_start, kv_len=kv_len, block_kv=block_kv)
    return out
