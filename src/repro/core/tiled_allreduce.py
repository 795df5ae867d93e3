"""Paper §4.2: tiling-AllReduce (T3).

In tensor-parallel inference every layer ends in ``partial = x @ W_row``
followed by an AllReduce.  The paper splits the B*S dimension into blocks
and issues one *B-allreduce* per block so communication of block i overlaps
compute of block i+1 (SDMA on Ascend; async ICI collectives + the XLA
latency-hiding scheduler on TPU).  Two paper details are preserved:

  * the FIRST block is smaller (``first_chunk_frac``) -- its AllReduce is
    the only one that cannot be overlapped, so shrinking it shrinks the
    exposed latency (paper: "assign smaller computation tasks to the first
    block");
  * the chunk count is bounded so per-block payloads stay large enough to
    saturate link bandwidth (paper: "enlarge the block size to achieve
    better bandwidth utilization").

Entry points:
  tiled_matmul_allreduce   -- chunked row-parallel matmul + psum (shard_map
                              body; works for O-proj and MLP down-proj).
  fused_attention_linear   -- the paper's fused attention+Linear+B-allreduce
                              block (head-sharded TP, benchmark/operator use).
  ring variant             -- explicit ppermute ring for scheduler-independent
                              overlap.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def chunk_sizes(total: int, n_chunks: int, first_frac: float = 0.5,
                align: int = 1) -> Sequence[int]:
    """Split ``total`` into ``n_chunks`` pieces, the first scaled by
    ``first_frac`` (paper: smaller head block), EVERY piece a multiple of
    ``align``.

    The split is computed in units of ``align`` so the trailing chunk is
    aligned too -- the old code appended a raw remainder, handing
    ``ring_matmul_allreduce`` (``piece = s // n``) and
    ``tiled_matmul_reducescatter`` (``psum_scatter`` needs axis-divisible
    chunks) a chunk they silently mis-split.  ``total`` itself must be a
    multiple of ``align``; callers with ragged totals pad first and slice
    the result (see ``ring_matmul_allreduce``).
    """
    align = max(align, 1)
    if total <= 0:
        raise ValueError(f"total must be positive, got {total}")
    if total % align:
        raise ValueError(
            f"total={total} is not a multiple of align={align}; pad the "
            f"leading dim to a multiple first and slice the result")
    units = total // align
    n_chunks = max(1, min(n_chunks, units))
    if n_chunks == 1:
        sizes = [total]
    else:
        base = units / (n_chunks - 1 + first_frac)
        first = min(max(1, int(base * first_frac)),
                    units - (n_chunks - 1))
        sizes = [first * align]
        remaining = units - first
        for i in range(n_chunks - 2):
            su = max(1, int(base))
            su = min(su, remaining - (n_chunks - 2 - i))
            sizes.append(su * align)
            remaining -= su
        sizes.append(remaining * align)
    assert sum(sizes) == total, sizes
    assert all(s > 0 for s in sizes), sizes
    assert all(s % align == 0 for s in sizes), sizes
    return sizes


def tiled_matmul_allreduce(x: jax.Array, w: jax.Array, axis_name: str, *,
                           n_chunks: int = 4, first_chunk_frac: float = 0.5,
                           precision=None) -> jax.Array:
    """psum_over_axis(x @ w), chunked over the leading dim of x.

    Per-device shard_map body.  x: (T, F_local); w: (F_local, D).
    Equivalent to ``jax.lax.psum(x @ w, axis_name)`` but emits one
    all-reduce per chunk, each overlappable with the next chunk's matmul.
    """
    t = x.shape[0]
    sizes = chunk_sizes(t, n_chunks, first_chunk_frac)
    outs = []
    off = 0
    for s in sizes:
        y = jax.lax.dynamic_slice_in_dim(x, off, s, 0) @ w
        outs.append(jax.lax.psum(y, axis_name))     # B-allreduce
        off += s
    return jnp.concatenate(outs, axis=0)


def single_matmul_allreduce(x: jax.Array, w: jax.Array,
                            axis_name: str) -> jax.Array:
    """Baseline: unfused matmul + one monolithic AllReduce."""
    return jax.lax.psum(x @ w, axis_name)


def matmul_allreduce(x: jax.Array, w: jax.Array, axis_name, *,
                     mode: str = "tiled", n_chunks: int = 4,
                     first_chunk_frac: float = 0.5) -> jax.Array:
    """Row-parallel matmul + AllReduce, dispatching on ``mode``.

    The shard_map-body entry point the tensor-parallel serving path uses
    for O-proj / down-proj partial sums.  ``axis_name`` may be a tuple of
    mesh axes (the paged TP mesh reduces over both its kv-head-group and
    page-row axes at once).  ``mode="tiled"`` emits one psum per chunk of
    the token dim (paper T3, overlappable); ``"single"`` is the
    monolithic baseline the benchmark compares against.
    """
    if mode == "single":
        return single_matmul_allreduce(x, w, axis_name)
    if mode != "tiled":
        raise ValueError(f"unknown allreduce mode {mode!r} "
                        "(expected 'tiled' or 'single')")
    return tiled_matmul_allreduce(x, w, axis_name, n_chunks=n_chunks,
                                  first_chunk_frac=first_chunk_frac)


def tiled_matmul_reducescatter(x: jax.Array, w: jax.Array, axis_name: str, *,
                               n_chunks: int = 4,
                               first_chunk_frac: float = 0.5) -> jax.Array:
    """Chunked row-parallel matmul + reduce-scatter (sequence-parallel TP).

    Output rows are scattered along the axis: (T, D) -> (T/axis, D).
    """
    t = x.shape[0]
    axis_size = jax.lax.axis_size(axis_name)
    if t % axis_size:
        raise ValueError(
            f"tiled_matmul_reducescatter: leading dim {t} must divide the "
            f"axis size {axis_size} -- psum_scatter splits every chunk "
            f"evenly over the axis; pad the rows first")
    sizes = chunk_sizes(t, n_chunks, first_chunk_frac, align=axis_size)
    outs = []
    off = 0
    for s in sizes:
        y = jax.lax.dynamic_slice_in_dim(x, off, s, 0) @ w
        outs.append(jax.lax.psum_scatter(y, axis_name, scatter_dimension=0,
                                         tiled=True))
        off += s
    return jnp.concatenate(outs, axis=0)


def ring_matmul_allreduce(x: jax.Array, w: jax.Array, axis_name: str, *,
                          n_chunks: int = 4) -> jax.Array:
    """Explicit overlap variant: reduce-scatter ring interleaved with the
    per-chunk matmuls, then all-gather.  The ppermute of chunk i runs while
    chunk i+1's matmul executes -- scheduler-independent overlap.

    Rows are padded to a multiple of the axis size (each chunk ring-
    scatters into ``s // n`` pieces) and the pad sliced off the result,
    so ragged token counts stay exact.
    """
    t = x.shape[0]
    n = jax.lax.axis_size(axis_name)
    pad = (-t) % n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    sizes = chunk_sizes(t + pad, n_chunks, 1.0, align=n)
    outs = []
    off = 0
    for s in sizes:
        y = jax.lax.dynamic_slice_in_dim(x, off, s, 0) @ w   # (s, D)
        # ring reduce-scatter over n-1 steps on this chunk; device i ends
        # holding fully-reduced piece i, so the trailing all-gather tiles
        # back in order.
        piece = s // n
        acc = jax.lax.dynamic_slice_in_dim(
            y, ((idx - 1) % n) * piece, piece, 0)
        for step in range(1, n):
            acc = jax.lax.ppermute(acc, axis_name, perm)
            src = jax.lax.dynamic_slice_in_dim(
                y, ((idx - step - 1) % n) * piece, piece, 0)
            acc = acc + src
        outs.append(jax.lax.all_gather(acc, axis_name, axis=0, tiled=True))
        off += s
    out = jnp.concatenate(outs, axis=0)
    return out[:t] if pad else out


def fused_attention_linear(q, k, v, w_o, axis_name: str, *,
                           n_chunks: int = 4, first_chunk_frac: float = 0.5,
                           causal: bool = True,
                           softcap: Optional[float] = None,
                           attention_fn: Optional[Callable] = None,
                           mode: str = "tiled") -> jax.Array:
    """Paper Fig. 4: fused attention + Linear + B-allreduce.

    Head-sharded TP shard_map body: q (B, S, H_local, D), k/v
    (B, S, Hkv_local, D), w_o (H_local*D, d_model).  The B*S dimension is
    split into blocks; each block runs attention -> O-proj -> B-allreduce,
    with block i's allreduce overlapping block i+1's compute.
    """
    from repro.core.fastattention import fast_attention
    b, s, h, d = q.shape
    attention_fn = attention_fn or (
        lambda qq, kk, vv, off: fast_attention(
            qq, kk, vv, causal=causal, softcap=softcap, q_offset=off,
            impl="reference"))
    if mode == "single":
        o = attention_fn(q, k, v, 0).reshape(b, s, h * d)
        return jax.lax.psum(o @ w_o, axis_name)
    # tile along S (paper tiles along B*S; S keeps causal offsets simple)
    sizes = chunk_sizes(s, n_chunks, first_chunk_frac)
    outs = []
    off = 0
    for sz in sizes:
        q_c = jax.lax.dynamic_slice_in_dim(q, off, sz, 1)
        kv_end = off + sz if causal else s
        k_c = jax.lax.dynamic_slice_in_dim(k, 0, kv_end, 1)
        v_c = jax.lax.dynamic_slice_in_dim(v, 0, kv_end, 1)
        o_c = attention_fn(q_c, k_c, v_c, off).reshape(b, sz, h * d)
        outs.append(jax.lax.psum(o_c @ w_o, axis_name))   # B-allreduce
        off += sz
    return jnp.concatenate(outs, axis=1)


def make_sharded_fused_block(mesh, axis_name: str = "model", **kw):
    """shard_map-wrapped fused_attention_linear over head-sharded inputs."""
    fn = functools.partial(fused_attention_linear, axis_name=axis_name, **kw)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, None, axis_name, None),
                  P(None, None, axis_name, None),
                  P(None, None, axis_name, None),
                  P(axis_name, None)),
        out_specs=P(None, None, None),
        check_vma=False)
