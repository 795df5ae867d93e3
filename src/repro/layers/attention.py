"""GQA attention layer wired to the FastAttention core.

Distribution (DESIGN.md §4.1): activations are sequence-sharded on the
`model` axis (context parallelism).  Q keeps its seq sharding; K/V are
constrained replicated along `model` (one small GQA KV all-gather per
layer), so the flash scan partitions cleanly over Q rows with zero extra
collectives.  At decode time the KV cache is instead sharded along its
*sequence* dim (`kv_seq -> model`); XLA decomposes the softmax/PV
reductions over the sharded dim into exactly the LSE-merge collectives of
core/distributed_decode.py.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.core.distributed_decode import (attend_with_positions,
                                           merge_partial_attention,
                                           paged_local_view,
                                           paged_shard_kv_positions)
from repro.core.fastattention import (default_paged_impl, fast_attention,
                                      fast_attention_decode,
                                      fast_attention_prefill_paged)
from repro.core.tiled_allreduce import matmul_allreduce
from repro.kernels.flash_decode.kernel import pages_transposed
from repro.layers import common, rotary
from repro.sharding.rules import constrain
from repro.sharding.tp import current_tp

# Decode KV-cache layout: "bshd" (token-major, default) or "bhsd"
# (head-major: the QK/PV contractions need no transposed copy of the
# cache -- decode hillclimb iteration, EXPERIMENTS.md §Perf cell 3).
KV_CACHE_LAYOUT = "bshd"


class KVCache(NamedTuple):
    k: jax.Array            # (B, S_max, Hkv, D) or (B, Hkv, S_max, D)
    v: jax.Array


def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": common.dense_init(ks[0], d, qd, dtype),
        "wk": common.dense_init(ks[1], d, kvd, dtype),
        "wv": common.dense_init(ks[2], d, kvd, dtype),
        "wo": common.dense_init(ks[3], qd, d, dtype, scale=qd ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kvd,), dtype)
        p["bv"] = jnp.zeros((kvd,), dtype)
    return p


def attention_logical(cfg: ModelConfig):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": (("attn_row", "heads"), (d, qd)),
        "wk": (("attn_row", "heads"), (d, kvd)),
        "wv": (("attn_row", "heads"), (d, kvd)),
        "wo": (("attn_row", "d_model"), (qd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = (("heads",), (qd,))
        p["bk"] = (("heads",), (kvd,))
        p["bv"] = (("heads",), (kvd,))
    return p


def _project_qkv(params, x, cfg: ModelConfig, positions):
    # head counts come from the weight shapes (-1), not the config: a
    # tensor-parallel shard passes its column-sliced projections through
    # the same code path (rope is per-head, independent of the count)
    b, s, _ = x.shape
    q = common.dense(x, params["wq"], params.get("bq"))
    k = common.dense(x, params["wk"], params.get("bk"))
    v = common.dense(x, params["wv"], params.get("bv"))
    q = q.reshape(b, s, -1, cfg.head_dim)
    k = k.reshape(b, s, -1, cfg.head_dim)
    v = v.reshape(b, s, -1, cfg.head_dim)
    if cfg.rope_type == "rope":
        q = rotary.apply_rope(q, positions, theta=cfg.rope_theta)
        k = rotary.apply_rope(k, positions, theta=cfg.rope_theta)
    elif cfg.rope_type == "mrope":
        q = rotary.apply_mrope(q, positions, sections=cfg.mrope_sections,
                               theta=cfg.rope_theta)
        k = rotary.apply_mrope(k, positions, sections=cfg.mrope_sections,
                               theta=cfg.rope_theta)
    return q, k, v


def apply_attention(params, x, cfg: ModelConfig, *,
                    positions, window: Optional[int] = None,
                    causal: bool = True,
                    impl: Optional[str] = None) -> jax.Array:
    """Training/prefill attention.  x: (B, S, D) seq-sharded."""
    impl = impl or cfg.attention_impl
    q, k, v = _project_qkv(params, x, cfg, positions)
    q = constrain(q, "batch", "seq", "heads", None)
    # context parallelism: KV replicated along `model` (GQA keeps it small)
    k = constrain(k, "batch", None, "heads", None)
    v = constrain(v, "batch", None, "heads", None)
    out = fast_attention(q, k, v, causal=causal, window=window,
                         softcap=cfg.attn_logit_softcap, impl=impl)
    out = constrain(out, "batch", "seq", "heads", None)
    b, s = x.shape[:2]
    out = out.reshape(b, s, cfg.q_dim)
    return common.dense(out, params["wo"])


def apply_cross_attention(params, x, enc_k, enc_v, cfg: ModelConfig, *,
                          impl: Optional[str] = None) -> jax.Array:
    """Decoder cross-attention over precomputed encoder K/V."""
    impl = impl or cfg.attention_impl
    b, s, _ = x.shape
    q = common.dense(x, params["wq"], params.get("bq"))
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    q = constrain(q, "batch", "seq", "heads", None)
    out = fast_attention(q, enc_k, enc_v, causal=False,
                         softcap=cfg.attn_logit_softcap, impl=impl)
    out = out.reshape(b, s, cfg.q_dim)
    return common.dense(out, params["wo"])


def project_cross_kv(params, enc_states, cfg: ModelConfig):
    b, s, _ = enc_states.shape
    k = common.dense(enc_states, params["wk"], params.get("bk"))
    v = common.dense(enc_states, params["wv"], params.get("bv"))
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    return constrain(k, "batch", None, "heads", None), \
        constrain(v, "batch", None, "heads", None)


def apply_attention_decode(params, x, cfg: ModelConfig, cache: KVCache, *,
                           pos, window: Optional[int] = None,
                           impl: Optional[str] = None):
    """One-token decode.  x: (B, 1, D); pos: scalar current position.

    Returns (out (B,1,D), new_cache).  The cache sequence dim carries the
    `kv_seq -> model` sharding (context-parallel decode).
    """
    impl = impl or cfg.attention_impl
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    if cfg.rope_type == "mrope":   # text continuation: t=h=w=pos
        positions = jnp.broadcast_to(positions, (3, b, 1))
    q, k_new, v_new = _project_qkv(params, x, cfg, positions)
    if KV_CACHE_LAYOUT == "bhsd":
        k = jax.lax.dynamic_update_slice(
            cache.k, k_new.astype(cache.k.dtype).transpose(0, 2, 1, 3),
            (0, 0, pos, 0))
        v = jax.lax.dynamic_update_slice(
            cache.v, v_new.astype(cache.v.dtype).transpose(0, 2, 1, 3),
            (0, 0, pos, 0))
        k = constrain(k, "batch", "heads", "kv_seq", None)
        v = constrain(v, "batch", "heads", "kv_seq", None)
    else:
        k = jax.lax.dynamic_update_slice(
            cache.k, k_new.astype(cache.k.dtype), (0, pos, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache.v, v_new.astype(cache.v.dtype), (0, pos, 0, 0))
        k = constrain(k, "batch", "kv_seq", "heads", None)
        v = constrain(v, "batch", "kv_seq", "heads", None)
    kv_len = jnp.full((b,), pos + 1, jnp.int32)
    out = fast_attention_decode(
        q, k, v, kv_len, window=window, softcap=cfg.attn_logit_softcap,
        impl="reference" if impl == "reference" else impl,
        layout=KV_CACHE_LAYOUT)
    out = out.reshape(b, 1, cfg.q_dim)
    return common.dense(out, params["wo"]), KVCache(k, v)


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int,
                  dtype) -> KVCache:
    if KV_CACHE_LAYOUT == "bhsd":
        shape = (batch, cfg.num_kv_heads, max_seq, cfg.head_dim)
    else:
        shape = (batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


# ---------------------------------------------------------------------------
# Paged decode: KV pages shared across sequences via a page table
# ---------------------------------------------------------------------------

def init_kv_pages(cfg: ModelConfig, num_pages: int, page_size: int,
                  dtype) -> KVCache:
    """Global page pools (Hkv, P, page_size, D).  Every sequence's cache
    is a subset of pages named by its page-table row; batch size does not
    appear in the storage shape -- the pool is the memory budget."""
    shape = (cfg.num_kv_heads, num_pages, page_size, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def write_kv_chunk(cache: KVCache, k_new, v_new, page_table, pos_start,
                   n_valid, layer=0, *, shard=0, shards: int = 1) -> KVCache:
    """Write a chunk of new K/V rows into the paged pools, in place.

    k_new/v_new: (B, S, Hkv, D), row i of sequence b at global position
    ``pos_start[b] + i``; rows ``i >= n_valid[b]`` (chunk padding) are
    not written.  The pools are one layer's (Hkv, P, ps, D) pair, or a
    layer-stacked (L, Hkv, P, ps, D) pair written at ``layer``.  The
    pages covering the valid positions must already be materialised in
    ``page_table``.

    Each page the chunk touches is read whole, the new rows are merged
    in, and the pages go back by one scatter whose indices are all arrays
    (layer, head, page) and whose window is a whole page in the layout
    the TPU keeps the pool in (``pages_transposed``), so XLA applies it
    to the donated pool in place.  A row-sized window makes XLA relayout
    the whole pool where pages are stored transposed, and a scatter of
    single elements is slower than the copy it saves.  A touched page
    with no valid row resolves to the scratch page 0 and goes back as it
    was read.

    With ``shards > 1`` the pools hold page rows ``[shard * ps_local,
    (shard + 1) * ps_local)`` of every page (a tensor-parallel page-row
    sub-shard; ``shard`` may be traced): a shard writes the rows it
    holds.
    """
    hkv, psl, d = cache.k.shape[-4], cache.k.shape[-2], cache.k.shape[-1]
    ps = psl * shards
    b, s = k_new.shape[:2]
    n_touch = (s + ps - 2) // ps + 1      # pages a run of s positions spans
    start = pos_start.astype(jnp.int32)
    block = start[:, None] // ps + jnp.arange(n_touch, dtype=jnp.int32)
    # the chunk row each local row of each touched page takes: (B, n, psl)
    row = (block[:, :, None] * ps + shard * psl - start[:, None, None]
           + jnp.arange(psl, dtype=jnp.int32))
    valid = (row >= 0) & (row < n_valid.astype(jnp.int32)[:, None, None])
    page = page_table[jnp.arange(b)[:, None],
                      jnp.minimum(block, page_table.shape[1] - 1)]
    page = jnp.where(valid.any(-1), page, 0)            # (B, n)
    shape = page.shape + (hkv,)
    idx = (jax.lax.broadcasted_iota(jnp.int32, shape, 2),
           jnp.broadcast_to(page[..., None], shape))
    if cache.k.ndim == 5:
        idx = (jnp.full(shape, layer, jnp.int32),) + idx
    transposed = pages_transposed(psl, d)
    src = jnp.clip(row, 0, s - 1)
    keep = valid[:, :, None, :, None]                   # (B, n, 1, psl, 1)

    def write(pool, rows):
        # (B, n, psl, Hkv, D) -> the pages' rows (B, n, Hkv, psl, D)
        new = jnp.moveaxis(
            rows.astype(pool.dtype)[jnp.arange(b)[:, None, None], src], 3, 2)
        mask = keep
        if transposed:
            pool, new, mask = (jnp.swapaxes(a, -1, -2)
                               for a in (pool, new, mask))
        pool = pool.at[idx].set(jnp.where(mask, new, pool[idx]))
        return jnp.swapaxes(pool, -1, -2) if transposed else pool

    return KVCache(write(cache.k, k_new), write(cache.v, v_new))


def apply_attention_prefill_paged(params, x, cfg: ModelConfig,
                                  cache: KVCache, *, page_table, pos_start,
                                  n_valid, layer=0,
                                  window: Optional[int] = None,
                                  impl: Optional[str] = None):
    """Chunked prefill against paged KV pools: one prompt chunk through
    full (not per-token) attention.

    x: (B, S_chunk, D) -- a fixed-size chunk, possibly padded past
    ``n_valid``; pos_start: (B,) int32 global position of each sequence's
    chunk start; page_table: (B, n_kv) int32.  The chunk's K/V rows are
    written into their pages (padding rows are not), then the
    chunk attends to every cached position <= its own through the page
    table.  All offsets are runtime values: one jit trace serves every
    chunk of every prompt.  The pools are one layer's (Hkv, P, ps, D)
    pair, or a layer-stacked (L, Hkv, P, ps, D) pair written and read in
    place at ``layer``.  Returns (out (B, S_chunk, D), new pools);
    output rows past ``n_valid`` are garbage and must be ignored.

    Under an active tensor-parallel context (sharding/tp.py) the pools
    are device-sharded and the whole layer runs as a shard_map body with
    an LSE merge -- see ``_tp_attention_prefill_paged``.
    """
    tpc = current_tp()
    if tpc is not None:
        return _tp_attention_prefill_paged(
            params, x, cfg, cache, page_table=page_table,
            pos_start=pos_start, n_valid=n_valid, layer=layer,
            window=window, tpc=tpc)
    impl = impl or default_paged_impl()
    b, s, _ = x.shape
    positions = pos_start.astype(jnp.int32)[:, None] + \
        jnp.arange(s, dtype=jnp.int32)[None]
    rope_pos = positions
    if cfg.rope_type == "mrope":   # text continuation: t=h=w=pos
        rope_pos = jnp.broadcast_to(positions, (3, b, s))
    q, k_new, v_new = _project_qkv(params, x, cfg, rope_pos)
    cache = write_kv_chunk(cache, k_new, v_new, page_table, pos_start,
                           n_valid, layer)
    kv_len = pos_start.astype(jnp.int32) + n_valid.astype(jnp.int32)
    out = fast_attention_prefill_paged(
        q, cache.k, cache.v, page_table, pos_start, kv_len, layer,
        window=window, softcap=cfg.attn_logit_softcap, impl=impl)
    out = out.reshape(b, s, cfg.q_dim)
    return common.dense(out, params["wo"]), cache


def apply_attention_decode_paged(params, x, cfg: ModelConfig,
                                 cache: KVCache, *, page_table, pos,
                                 layer=0, window: Optional[int] = None,
                                 impl: Optional[str] = None):
    """One-token decode against paged KV pools.

    x: (B, 1, D); pos: (B,) int32 per-sequence positions (ragged batch --
    unlike the dense path there is no shared scalar position);
    page_table: (B, n_kv) int32.  The new K/V row is written into page
    ``page_table[b, pos // page_size]`` at offset ``pos % page_size``;
    attention then reads kv_len = pos + 1 tokens through the table.  The
    pools are one layer's (Hkv, P, ps, D) pair, or a layer-stacked
    (L, Hkv, P, ps, D) pair written and read in place at ``layer``.
    Returns (out (B, 1, D), new KVCache of pools).

    Under an active tensor-parallel context (sharding/tp.py) the pools
    are device-sharded and the whole layer runs as a shard_map body with
    an LSE merge -- see ``_tp_attention_decode_paged``.
    """
    tpc = current_tp()
    if tpc is not None:
        return _tp_attention_decode_paged(
            params, x, cfg, cache, page_table=page_table, pos=pos,
            layer=layer, window=window, tpc=tpc)
    impl = impl or default_paged_impl()
    b = x.shape[0]
    positions = pos.astype(jnp.int32)[:, None]
    if cfg.rope_type == "mrope":   # text continuation: t=h=w=pos
        positions = jnp.broadcast_to(positions, (3, b, 1))
    q, k_new, v_new = _project_qkv(params, x, cfg, positions)
    cache = write_kv_chunk(cache, k_new, v_new, page_table, pos,
                           jnp.ones_like(pos), layer)
    kv_len = pos.astype(jnp.int32) + 1
    out = fast_attention_decode(
        q, cache.k, cache.v, kv_len, window=window,
        softcap=cfg.attn_logit_softcap, impl=impl, page_table=page_table,
        layer=layer)
    out = out.reshape(b, 1, cfg.q_dim)
    return common.dense(out, params["wo"]), cache


# ---------------------------------------------------------------------------
# Tensor-parallel paged attention (shard_map bodies over the TP mesh)
# ---------------------------------------------------------------------------
#
# The pools are sharded (kv_heads -> head-group axis, within-page rows ->
# page-row axis); weights and activations enter replicated and each shard
# slices its own projection columns by axis index.  Every shard attends
# over its local KV rows only; the page-row sub-shards of a kv-head group
# merge their partial outputs exactly via the log-sum-exp combination
# (core/distributed_decode.merge_partial_attention), then the O-proj runs
# row-parallel over per-shard query-head slices with a tiling-AllReduce
# (core/tiled_allreduce.matmul_allreduce) over the whole mesh.

def _tp_pool_spec(plan, ndim: int) -> P:
    """(Hkv, P, ps, D) pools: kv heads over the head-group axis,
    within-page rows over the page-row axis; a layer-stacked
    (L, Hkv, P, ps, D) pool keeps its layer dim whole.  The page axis
    stays third from the end (serving/pressure.py PAGE_AXIS_FROM_END)."""
    heads_ax, seq_ax = plan.axes
    spec = (heads_ax, None, seq_ax, None)
    return P(*((None,) * (ndim - 4) + spec))


def _tp_layer_view(pool, layer, table):
    """The dense (B, Hkv_local, n_kv * ps_local, D) view of this shard's
    pool block at ``layer`` (stacked pools) for the LSE-merge attention."""
    return paged_local_view(pool[layer] if pool.ndim == 5 else pool, table)


def _tp_slice_attn_params(params, cfg: ModelConfig, gi, si, plan):
    """This shard's projection slices.  QKV are column-parallel over the
    kv-head group (all ``s`` page-row sub-shards of a group compute the
    group's full Q -- they need every query head for the LSE merge);
    the O-proj is row-parallel over the shard's 1/s query-head slice.
    Head blocks are contiguous column/row runs, so slices are dynamic
    (``gi``/``si`` are traced axis indices)."""
    dh = cfg.head_dim
    kvl = cfg.num_kv_heads // plan.g       # kv heads per group
    hq_g = cfg.num_heads // plan.g         # q heads per group
    hq_s = hq_g // plan.s                  # q heads per O-proj row slice
    q0, k0 = gi * hq_g * dh, gi * kvl * dh

    def cols(w, off, n):
        return jax.lax.dynamic_slice_in_dim(w, off, n, axis=1)

    p = {"wq": cols(params["wq"], q0, hq_g * dh),
         "wk": cols(params["wk"], k0, kvl * dh),
         "wv": cols(params["wv"], k0, kvl * dh)}
    if "bq" in params:
        p["bq"] = jax.lax.dynamic_slice_in_dim(params["bq"], q0,
                                               hq_g * dh, 0)
        p["bk"] = jax.lax.dynamic_slice_in_dim(params["bk"], k0,
                                               kvl * dh, 0)
        p["bv"] = jax.lax.dynamic_slice_in_dim(params["bv"], k0,
                                               kvl * dh, 0)
    o0 = (gi * hq_g + si * hq_s) * dh      # global first row of the slice
    wo = jax.lax.dynamic_slice_in_dim(params["wo"], o0, hq_s * dh, axis=0)
    return p, wo, hq_s


def _tp_o_proj(merged, wo_loc, si, hq_s, dtype, plan):
    """Row-parallel O-proj of the merged attention output.

    merged: (B, Hq_group, Sq, D) f32, identical on every page-row
    sub-shard of the group after the LSE merge; each shard contributes
    its 1/s query-head slice against its wo row block, summed over the
    WHOLE mesh (g*s disjoint row blocks) by the tiling-AllReduce."""
    b, _, sq, d = merged.shape
    sl = jax.lax.dynamic_slice_in_dim(merged, si * hq_s, hq_s, axis=1)
    o = sl.astype(dtype).transpose(0, 2, 1, 3).reshape(b * sq, hq_s * d)
    y = matmul_allreduce(o, wo_loc, plan.axes, mode=plan.collectives,
                         n_chunks=plan.ar_chunks,
                         first_chunk_frac=plan.first_chunk_frac)
    return y.reshape(b, sq, -1)


def _tp_attention_decode_paged(params, x, cfg: ModelConfig,
                               cache: KVCache, *, page_table, pos, layer,
                               window: Optional[int], tpc):
    plan, mesh = tpc.plan, tpc.mesh
    heads_ax, seq_ax = plan.axes
    pool_spec = _tp_pool_spec(plan, cache.k.ndim)

    def body(prm, xb, kp, vp, table, posb, lyr):
        gi = jax.lax.axis_index(heads_ax)
        si = jax.lax.axis_index(seq_ax)
        b = xb.shape[0]
        sp, wo_loc, hq_s = _tp_slice_attn_params(prm, cfg, gi, si, plan)
        positions = posb.astype(jnp.int32)[:, None]
        if cfg.rope_type == "mrope":   # text continuation: t=h=w=pos
            positions = jnp.broadcast_to(positions, (3, b, 1))
        q, k_new, v_new = _project_qkv(sp, xb, cfg, positions)
        # only the sub-shard holding the row's within-page offset writes
        # it
        kp, vp = write_kv_chunk(KVCache(kp, vp), k_new, v_new, table, posb,
                                jnp.ones_like(posb), lyr, shard=si,
                                shards=plan.s)
        psl = kp.shape[-2]
        ps = psl * plan.s
        kv_len = posb.astype(jnp.int32) + 1
        kv_pos = paged_shard_kv_positions(table.shape[1], ps, psl, si)
        out, lse = attend_with_positions(
            q.transpose(0, 2, 1, 3), _tp_layer_view(kp, lyr, table),
            _tp_layer_view(vp, lyr, table),
            q_positions=(kv_len - 1)[:, None], kv_positions=kv_pos,
            kv_len=kv_len, causal=True, window=window,
            softcap=cfg.attn_logit_softcap)
        merged = merge_partial_attention(out, lse, seq_ax)
        return _tp_o_proj(merged, wo_loc, si, hq_s, xb.dtype, plan), kp, vp

    out, k, v = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), pool_spec, pool_spec, P(), P(), P()),
        out_specs=(P(), pool_spec, pool_spec),
        check_vma=False)(params, x, cache.k, cache.v, page_table, pos,
                         jnp.asarray(layer, jnp.int32))
    return out, KVCache(k, v)


def _tp_attention_prefill_paged(params, x, cfg: ModelConfig,
                                cache: KVCache, *, page_table, pos_start,
                                n_valid, layer, window: Optional[int],
                                tpc):
    plan, mesh = tpc.plan, tpc.mesh
    heads_ax, seq_ax = plan.axes
    pool_spec = _tp_pool_spec(plan, cache.k.ndim)

    def body(prm, xb, kp, vp, table, p0, nv, lyr):
        gi = jax.lax.axis_index(heads_ax)
        si = jax.lax.axis_index(seq_ax)
        b, s, _ = xb.shape
        sp, wo_loc, hq_s = _tp_slice_attn_params(prm, cfg, gi, si, plan)
        positions = p0.astype(jnp.int32)[:, None] + \
            jnp.arange(s, dtype=jnp.int32)[None]
        rope_pos = positions
        if cfg.rope_type == "mrope":   # text continuation: t=h=w=pos
            rope_pos = jnp.broadcast_to(positions, (3, b, s))
        q, k_new, v_new = _project_qkv(sp, xb, cfg, rope_pos)
        # each page-row sub-shard writes the chunk rows it holds
        kp, vp = write_kv_chunk(KVCache(kp, vp), k_new, v_new, table, p0,
                                nv, lyr, shard=si, shards=plan.s)
        psl = kp.shape[-2]
        ps = psl * plan.s
        kv_len = p0.astype(jnp.int32) + nv.astype(jnp.int32)
        kv_pos = paged_shard_kv_positions(table.shape[1], ps, psl, si)
        out, lse = attend_with_positions(
            q.transpose(0, 2, 1, 3), _tp_layer_view(kp, lyr, table),
            _tp_layer_view(vp, lyr, table),
            q_positions=positions, kv_positions=kv_pos, kv_len=kv_len,
            causal=True, window=window, softcap=cfg.attn_logit_softcap)
        merged = merge_partial_attention(out, lse, seq_ax)
        return _tp_o_proj(merged, wo_loc, si, hq_s, xb.dtype, plan), kp, vp

    out, k, v = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), pool_spec, pool_spec, P(), P(), P(), P()),
        out_specs=(P(), pool_spec, pool_spec),
        check_vma=False)(params, x, cache.k, cache.v, page_table,
                         pos_start, n_valid, jnp.asarray(layer, jnp.int32))
    return out, KVCache(k, v)
