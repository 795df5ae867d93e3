"""Context-parallel decode: KV cache sharded along its sequence dimension.

Beyond-paper extension (DESIGN.md §2.6): the paper tiles the KV sequence
within one NPU; here the same online-softmax decomposition is promoted to
the distributed level.  Each `model`-axis shard holds a contiguous slice of
the KV cache, runs flash-decode locally with a log-sum-exp, and partial
outputs merge exactly:

    m  = pmax(lse_i)
    out = psum(exp(lse_i - m) * out_i) / psum(exp(lse_i - m) * l_i ... )

(the denominator folds into the weights since out_i is already normalized
by its local softmax sum).

This removes the per-device KV-cache replication that otherwise caps
context length -- the distributed analogue of the paper's 16K -> 256K
claim -- and is what makes decode_32k@b128 and long_500k fit on v5e.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def attend_with_positions(q, k, v, *, q_positions, kv_positions, kv_len,
                          causal: bool = True,
                          window: Optional[int] = None,
                          softcap: Optional[float] = None,
                          scale: Optional[float] = None):
    """Attention over a KV slice whose global token positions are
    arbitrary (the paged-TP building block).

    A page-row sub-shard's gathered KV view is *strided* in global
    positions (it holds rows ``[si*ps_l, (si+1)*ps_l)`` of every page),
    so masks must be driven by an explicit position vector rather than
    an offset + arange.

    q: (B, Hq, Sq, D); k/v: (B, Hkv, K, D); q_positions: (B, Sq) int32
    global query positions; kv_positions: (K,) int32 global key
    positions; kv_len: (B,) int32 valid global lengths.  Returns
    ``(out, lse)`` -- out (B, Hq, Sq, D) f32, locally softmax-
    normalized; lse (B, Hq, Sq) the local log-sum-exp, NEG_INF where no
    key was valid (so the cross-shard merge weighs the shard at zero).
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    n_rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if n_rep > 1:
        kf = jnp.repeat(kf, n_rep, axis=1)
        vf = jnp.repeat(vf, n_rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    kv_pos = kv_positions.astype(jnp.int32)[None, None, :]     # (1, 1, K)
    q_pos = q_positions.astype(jnp.int32)[:, :, None]          # (B, Sq, 1)
    mask = kv_pos < jnp.asarray(kv_len, jnp.int32).reshape(-1, 1, 1)
    if causal:
        mask = mask & (q_pos >= kv_pos)
    if window is not None:
        mask = mask & (q_pos - kv_pos < window)
    maskb = mask[:, None]                                # (B, 1, Sq, K)
    s = jnp.where(maskb, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(jnp.where(maskb, p, 0.0), axis=-1)
    l_safe = jnp.where(l == 0, 1.0, l)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf) / l_safe[..., None]
    lse = jnp.where(l == 0, NEG_INF, m + jnp.log(l_safe))
    return out, lse


def merge_partial_attention(out, lse, axis_name):
    """Exact cross-shard merge of locally-normalized partial attention.

    The log-sum-exp combination (module docstring): ``m = pmax(lse);
    w = exp(lse - m); psum(out * w) / psum(w)``.  ``axis_name`` may be a
    tuple of mesh axes or carry ``axis_index_groups`` semantics via a
    sub-axis of a 2-D mesh (the paged-TP path merges over the page-row
    axis only, within each kv-head group).  out: lse.shape + (D,).
    """
    m = jax.lax.pmax(lse, axis_name)
    w = jnp.exp(lse - m)
    num = jax.lax.psum(out * w[..., None], axis_name)
    den = jax.lax.psum(w, axis_name)
    den = jnp.where(den == 0, 1.0, den)
    return num / den[..., None]


# ---------------------------------------------------------------------------
# Paged entry points (the TP serving path's shard_map-body helpers)
# ---------------------------------------------------------------------------

def paged_local_view(pages, page_table):
    """Local analogue of kernels/flash_decode/ref.paged_gather for one
    shard's pool block: pages (Hkv_local, P, ps_local, D), page_table
    (B, n_kv) int32 -> (B, Hkv_local, n_kv * ps_local, D)."""
    g = pages[:, page_table]                 # (H, B, n_kv, ps_l, D)
    h, b, n_kv, psl, d = g.shape
    return g.transpose(1, 0, 2, 3, 4).reshape(b, h, n_kv * psl, d)


def paged_shard_kv_positions(n_kv: int, page_size: int, rows_local: int,
                             shard_index):
    """Global token position of every row of a page-row sub-shard's
    gathered view: view row j sits in logical page ``j // rows_local``
    at within-page offset ``shard_index * rows_local + j % rows_local``.
    ``shard_index`` may be a traced ``axis_index``.  Returns (K,) int32
    with K = n_kv * rows_local."""
    j = jnp.arange(n_kv * rows_local, dtype=jnp.int32)
    return ((j // rows_local) * page_size
            + shard_index * rows_local + j % rows_local)


def _local_decode_with_lse(q, k, v, start, stop, *, window, softcap, scale,
                           global_len):
    """Decode attention over a local KV shard covering [start, stop).

    q: (B, Hq, D); k/v: (B, Hkv, S_local, D); returns (out, lse) where out
    is locally softmax-normalized and lse the local log-sum-exp.
    """
    glen = jnp.asarray(global_len, jnp.int32).reshape(-1)
    kv_pos = start + jnp.arange(k.shape[2], dtype=jnp.int32)
    # decode masks (pos < len, window back from len) are the causal/
    # window masks at q_position = len - 1
    out, lse = attend_with_positions(
        q[:, :, None], k, v, q_positions=(glen - 1)[:, None],
        kv_positions=kv_pos, kv_len=glen, causal=True, window=window,
        softcap=softcap, scale=scale)
    return out[:, :, 0], lse[:, :, 0]


def cp_decode_body(q, k_shard, v_shard, kv_len, *, axis_name: str,
                   window: Optional[int] = None,
                   softcap: Optional[float] = None,
                   scale: Optional[float] = None,
                   global_seq: int = 0):
    """shard_map body: q replicated, k/v sharded along seq on axis_name."""
    idx = jax.lax.axis_index(axis_name)
    s_local = k_shard.shape[2]
    start = idx * s_local
    out, lse = _local_decode_with_lse(
        q, k_shard, v_shard, start, start + s_local, window=window,
        softcap=softcap, scale=scale, global_len=kv_len)
    return merge_partial_attention(out, lse, axis_name).astype(q.dtype)


def context_parallel_decode(mesh, q, k_cache, v_cache, kv_len, *,
                            axis_name: str = "model",
                            batch_axes=("data",),
                            window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            scale: Optional[float] = None):
    """Distributed decode attention.

    q: (B, Hq, D); caches (B, Hkv, S, D) -- S sharded over ``axis_name``,
    B sharded over ``batch_axes``.  Returns (B, Hq, D).
    """
    body = functools.partial(
        cp_decode_body, axis_name=axis_name, window=window,
        softcap=softcap, scale=scale, global_seq=k_cache.shape[2])
    ba = tuple(a for a in batch_axes if a in mesh.axis_names)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(ba, None, None), P(ba, None, axis_name, None),
                  P(ba, None, axis_name, None), P(ba)),
        out_specs=P(ba, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, kv_len)
