"""Public FastAttention API used by the model layers.

Model layers use (B, S, H, D) activations; kernels use (B, H, S, D).
This facade handles the transposition, implementation dispatch and the
serve-time (decode) path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def fast_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True,
                   window: Optional[int] = None,
                   softcap: Optional[float] = None,
                   scale: Optional[float] = None,
                   q_offset: int = 0,
                   kv_valid: Optional[int] = None,
                   impl: str = "reference",
                   block_q: int = 256,
                   block_kv1: int = 1024,
                   block_kv2: int = 256) -> jax.Array:
    """Attention over (B, S, H, D) tensors.  Returns (B, Sq, Hq, D).

    ``kv_valid`` (static) masks K/V rows past that length (e.g. the
    zero-padded tail of a gathered paged view)."""
    from repro.kernels.fastattn.ops import fastattn
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    out = fastattn(qT, kT, vT, causal, window, softcap, scale, q_offset,
                   block_q, block_kv1, block_kv2, impl, kv_valid)
    return out.transpose(0, 2, 1, 3)


def default_paged_impl() -> str:
    """Best paged impl for the current backend: the compiled Pallas
    kernel on TPU, the jittable gather-reference everywhere else.  The
    kernel runs off-TPU only when asked for by name ("paged_interpret")."""
    return "paged" if jax.default_backend() == "tpu" else "paged_reference"


def fast_attention_prefill_paged(q: jax.Array, k_pages: jax.Array,
                                 v_pages: jax.Array, page_table: jax.Array,
                                 pos_start: jax.Array, kv_len: jax.Array,
                                 layer=0, *,
                                 window: Optional[int] = None,
                                 softcap: Optional[float] = None,
                                 scale: Optional[float] = None,
                                 impl: str = "paged_reference",
                                 block_q: int = 256) -> jax.Array:
    """Chunked-prefill attention of one prompt chunk against the paged
    KV pools (the chunk's own K/V rows must already be scattered in).

    q: (B, Sq, Hq, D) layer-layout chunk queries; pages
    (Hkv, P, page_size, D), or layer-stacked (L, Hkv, P, page_size, D)
    read in place at ``layer``; page_table (B, n_kv) int32; pos_start /
    kv_len: (B,) int32 *runtime* offsets -- one jit trace serves every
    chunk position of every prompt length.  "paged" runs the compiled
    Pallas kernel (scalar-prefetched page table; TPU only),
    "paged_interpret" the same kernel in the Pallas interpreter;
    "paged_reference" gathers the owned pages and runs the online-softmax
    flash reference -- the jittable CPU path.  Returns (B, Sq, Hq, D).
    """
    qT = q.transpose(0, 2, 1, 3)
    if impl == "paged_reference":
        from repro.kernels.flash_decode.ref import paged_prefill_reference
        out = paged_prefill_reference(
            qT, k_pages, v_pages, page_table, pos_start, kv_len, layer,
            window=window, softcap=softcap, scale=scale)
    elif impl in ("paged", "paged_interpret"):
        from repro.kernels.fastattn.ops import fastattn_paged_prefill
        out = fastattn_paged_prefill(
            qT, k_pages, v_pages, page_table, pos_start, kv_len, layer,
            window=window, softcap=softcap, scale=scale, block_q=block_q,
            interpret=impl == "paged_interpret")
    else:
        raise ValueError(f"unknown paged prefill impl {impl!r}")
    return out.transpose(0, 2, 1, 3)


def fast_attention_decode(q: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, kv_len: jax.Array, *,
                          window: Optional[int] = None,
                          softcap: Optional[float] = None,
                          scale: Optional[float] = None,
                          impl: str = "reference",
                          block_kv: int = 512,
                          layout: str = "bshd",
                          page_table: Optional[jax.Array] = None,
                          layer=0) -> jax.Array:
    """Single-token decode attention.

    q: (B, 1, Hq, D); caches (B, S, Hkv, D) ["bshd"] or (B, Hkv, S, D)
    ["bhsd", head-major: no transpose before the contraction]; kv_len (B,).
    Returns (B, 1, Hq, D).

    With ``impl in ("paged", "paged_interpret", "paged_reference")`` the
    caches are instead global page pools (Hkv, P, page_size, D) shared by
    every sequence (or layer-stacked (L, Hkv, P, page_size, D) pools read
    in place at ``layer``), and ``page_table`` (B, n_kv) int32 maps each
    sequence's logical KV block to its physical page (serving/paged_cache
    owns the table).  "paged" runs the compiled Pallas kernel (TPU
    only), "paged_interpret" the same kernel in the Pallas interpreter;
    "paged_reference" gathers the owned pages into a dense view and
    reuses the dense oracle -- the jittable CPU path.

    The reference path works IN PLACE on the (B, S, Hkv, D) bf16 cache --
    no transpose, no GQA expansion, no f32 copy; einsums accumulate in f32
    (decode is HBM-bound: every extra cache copy doubles the memory term).
    The sequence dim may carry the `kv_seq -> model` sharding; XLA then
    decomposes the max/sum/PV reductions into the LSE-merge collectives of
    core/distributed_decode.py.
    """
    if impl in ("paged", "paged_interpret", "paged_reference"):
        if page_table is None:
            raise ValueError(f"impl={impl!r} requires a page_table")
        if impl == "paged_reference":
            from repro.kernels.flash_decode.ref import paged_decode_reference
            out = paged_decode_reference(
                q.transpose(0, 2, 1, 3), k_cache, v_cache, page_table,
                kv_len, layer, window=window, softcap=softcap, scale=scale)
            return out.transpose(0, 2, 1, 3)
        from repro.kernels.flash_decode.ops import paged_flash_decode
        out = paged_flash_decode(
            q.transpose(0, 2, 1, 3)[:, :, 0], k_cache, v_cache, page_table,
            kv_len, layer, window=window, softcap=softcap, scale=scale,
            interpret=impl == "paged_interpret")[:, :, None]
        return out.transpose(0, 2, 1, 3)

    if impl in ("pallas", "interpret"):
        from repro.kernels.flash_decode.ops import flash_decode
        qT = q.transpose(0, 2, 1, 3)
        if layout == "bhsd":
            kT, vT = k_cache, v_cache
        else:
            kT = k_cache.transpose(0, 2, 1, 3)
            vT = v_cache.transpose(0, 2, 1, 3)
        out = flash_decode(qT[:, :, 0], kT, vT, kv_len,
                           window=window, softcap=softcap, scale=scale,
                           block_kv=block_kv,
                           interpret=(impl == "interpret"))[:, :, None]
        return out.transpose(0, 2, 1, 3)

    b, _, hq, d = q.shape
    if layout == "bhsd":
        hkv, s = k_cache.shape[1], k_cache.shape[2]
    else:
        s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, g, d)
    kv_eq = "bhgd,bhsd->bhgs" if layout == "bhsd" else "bhgd,bshd->bhgs"
    logits = jnp.einsum(kv_eq, qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    pos = jnp.arange(s)[None, None, None, :]
    lens = jnp.asarray(kv_len).reshape(b, 1, 1, 1)
    mask = pos < lens
    if window is not None:
        mask = mask & (pos >= lens - window)
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = (p / jnp.where(l == 0, 1.0, l)).astype(k_cache.dtype)
    pv_eq = "bhgs,bhsd->bhgd" if layout == "bhsd" else "bhgs,bshd->bhgd"
    out = jnp.einsum(pv_eq, p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)
