"""Jit'd wrappers for the flash-decode kernels (inference only, no vjp)."""
from __future__ import annotations

from typing import Optional

from repro.kernels.flash_decode.kernel import (flash_decode_fwd,
                                               paged_flash_decode_fwd)


def flash_decode(q, k_cache, v_cache, kv_len, *,
                 window: Optional[int] = None,
                 softcap: Optional[float] = None,
                 scale: Optional[float] = None,
                 block_kv: int = 512,
                 interpret: bool = False):
    """Decode attention: q (B, Hq, D) against (B, Hkv, S, D) caches."""
    return flash_decode_fwd(
        q, k_cache, v_cache, kv_len, window=window, softcap=softcap,
        scale=scale, block_kv=block_kv, interpret=interpret)


def paged_flash_decode(q, k_pages, v_pages, page_table, kv_len, layer=0, *,
                       window: Optional[int] = None,
                       softcap: Optional[float] = None,
                       scale: Optional[float] = None,
                       interpret: bool = False):
    """Decode attention over a paged cache: q (B, Hq, D), pages
    (Hkv, P, page_size, D) or layer-stacked (L, Hkv, P, page_size, D)
    read at ``layer``, page_table (B, n_kv) int32."""
    return paged_flash_decode_fwd(
        q, k_pages, v_pages, page_table, kv_len, layer, window=window,
        softcap=softcap, scale=scale, interpret=interpret)
