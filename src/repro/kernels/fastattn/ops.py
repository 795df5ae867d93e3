"""Jit'd public wrapper for the FastAttention kernel.

``fastattn`` dispatches between the Pallas TPU kernel, interpret mode
(CPU validation), and the pure-jnp flash reference, and attaches a
recompute-based backward (custom_vjp) so the op is usable in training.
``fastattn_paged_prefill`` is the inference-only chunked-prefill variant
that reads K/V straight from the paged pools through the page table.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.fastattn import kernel as _kernel
from repro.kernels.fastattn import ref as _ref


@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 12))
def fastattn(q, k, v,
             causal: bool = True,
             window: Optional[int] = None,
             softcap: Optional[float] = None,
             scale: Optional[float] = None,
             q_offset: int = 0,
             block_q: int = 256,
             block_kv1: int = 1024,
             block_kv2: int = 256,
             impl: str = "pallas",
             kv_valid: Optional[int] = None):
    """FastAttention: (B, Hq, Sq, D) x (B, Hkv, Skv, D) -> (B, Hq, Sq, D).

    impl: 'pallas' (TPU), 'interpret' (Pallas on CPU for validation), or
    'reference' (pure jnp; used for CPU dry-runs / as backward).
    ``kv_valid`` (static) masks K/V rows past that length -- the tail of a
    gathered paged view whose last page is only partially filled.
    """
    if impl in ("pallas", "interpret"):
        return _kernel.fastattn_fwd(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, q_offset=q_offset, kv_valid=kv_valid,
            block_q=block_q, block_kv1=block_kv1, block_kv2=block_kv2,
            interpret=(impl == "interpret"))
    if impl != "reference":
        raise ValueError(f"unknown fastattn impl {impl!r}")
    return _ref.flash_reference(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        q_offset=q_offset, kv_len=kv_valid, block_kv=block_kv1)


def _fwd(q, k, v, causal, window, softcap, scale, q_offset,
         block_q, block_kv1, block_kv2, impl, kv_valid):
    out = fastattn(q, k, v, causal, window, softcap, scale, q_offset,
                   block_q, block_kv1, block_kv2, impl, kv_valid)
    return out, (q, k, v)


def _bwd(causal, window, softcap, scale, q_offset,
         block_q, block_kv1, block_kv2, impl, kv_valid, res, g):
    # Recompute-based backward through the flash reference (same numerics,
    # linear memory).  On TPU the fwd ran the Pallas kernel; the bwd is a
    # standard-XLA chunked recompute -- documented in DESIGN.md §7.
    q, k, v = res

    def f(q, k, v):
        return _ref.flash_reference(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, q_offset=q_offset, kv_len=kv_valid,
            block_kv=block_kv1)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


fastattn.defvjp(_fwd, _bwd)


def fastattn_paged_prefill(q, k_pages, v_pages, page_table, pos_start,
                           kv_len, layer=0, *,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           block_q: int = 256,
                           interpret: bool = False):
    """Chunked-prefill attention against the paged KV pools (no vjp --
    serving only).  q: (B, Hq, Sq, D); pages (Hkv, P, page_size, D) or
    layer-stacked (L, Hkv, P, page_size, D) read at ``layer``;
    page_table (B, n_kv) int32; pos_start/kv_len (B,) int32 runtime
    offsets (scalar-prefetched: one trace per chunk *shape*, not per
    chunk position)."""
    return _kernel.paged_prefill_fwd(
        q, k_pages, v_pages, page_table, pos_start, kv_len, layer,
        window=window, softcap=softcap, scale=scale, block_q=block_q,
        interpret=interpret)
