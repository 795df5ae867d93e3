"""Per-layer block definitions + caches for every block kind.

A block is (init, logical, apply, init_cache); models/lm.py composes
segments of homogeneous blocks with lax.scan.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.layers import attention as attn_mod
from repro.layers import mlp as mlp_mod
from repro.layers import moe as moe_mod
from repro.layers import ssm as ssm_mod
from repro.layers.norms import apply_norm, init_norm, norm_logical
from repro.sharding.rules import constrain


def _window(cfg: ModelConfig, kind: str) -> Optional[int]:
    return cfg.window_size if kind.endswith("local") else None


# ---------------------------------------------------------------------------
# init / logical
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: str, dtype):
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: dict = {"ln1": init_norm(d, cfg.norm_type, dtype)}
    if kind in ("attn", "attn_local", "moe", "hymba", "hymba_local"):
        p["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
        p["ln2"] = init_norm(d, cfg.norm_type, dtype)
        if kind == "moe":
            p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
        elif cfg.d_ff:
            p["mlp"] = mlp_mod.init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_type,
                                        dtype)
        if cfg.post_norm:
            p["ln1_post"] = init_norm(d, cfg.norm_type, dtype)
            p["ln2_post"] = init_norm(d, cfg.norm_type, dtype)
    if kind in ("hymba", "hymba_local"):
        p["mamba"] = ssm_mod.init_mamba(ks[2], cfg, dtype)
        p["norm_attn"] = init_norm(d, cfg.norm_type, dtype)
        p["norm_mamba"] = init_norm(d, cfg.norm_type, dtype)
    if kind == "mlstm":
        p["cell"] = ssm_mod.init_mlstm(ks[3], cfg, dtype)
    if kind == "slstm":
        p["cell"] = ssm_mod.init_slstm(ks[4], cfg, dtype)
    return p


def block_logical(cfg: ModelConfig, kind: str):
    d = cfg.d_model
    p: dict = {"ln1": norm_logical(d, cfg.norm_type)}
    if kind in ("attn", "attn_local", "moe", "hymba", "hymba_local"):
        p["attn"] = attn_mod.attention_logical(cfg)
        p["ln2"] = norm_logical(d, cfg.norm_type)
        if kind == "moe":
            p["moe"] = moe_mod.moe_logical(cfg)
        elif cfg.d_ff:
            p["mlp"] = mlp_mod.mlp_logical(d, cfg.d_ff, cfg.mlp_type)
        if cfg.post_norm:
            p["ln1_post"] = norm_logical(d, cfg.norm_type)
            p["ln2_post"] = norm_logical(d, cfg.norm_type)
    if kind in ("hymba", "hymba_local"):
        p["mamba"] = ssm_mod.mamba_logical(cfg)
        p["norm_attn"] = norm_logical(d, cfg.norm_type)
        p["norm_mamba"] = norm_logical(d, cfg.norm_type)
    if kind == "mlstm":
        p["cell"] = ssm_mod.mlstm_logical(cfg)
    if kind == "slstm":
        p["cell"] = ssm_mod.slstm_logical(cfg)
    return p


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                     dtype):
    if kind in ("attn", "attn_local", "moe"):
        return attn_mod.init_kv_cache(cfg, batch, max_seq, dtype)
    if kind in ("hymba", "hymba_local"):
        di, n = cfg.q_dim, cfg.ssm_state_size
        return {
            "kv": attn_mod.init_kv_cache(cfg, batch, max_seq, dtype),
            "mamba": ssm_mod.MambaState(
                h=jnp.zeros((batch, di, n), jnp.float32),
                conv=jnp.zeros((batch, cfg.conv_kernel - 1, di), dtype)),
        }
    if kind == "mlstm":
        di = int(cfg.d_model * cfg.mlstm_proj_factor)
        nh, hd = cfg.num_heads, int(cfg.d_model * cfg.mlstm_proj_factor
                                    ) // cfg.num_heads
        return ssm_mod.MLSTMState(
            c=jnp.zeros((batch, nh, hd, hd), jnp.float32),
            n=jnp.zeros((batch, nh, hd), jnp.float32),
            m=jnp.full((batch, nh), -1e30, jnp.float32),
            conv=jnp.zeros((0,), dtype))
    if kind == "slstm":
        di = int(cfg.d_model * cfg.mlstm_proj_factor)
        return ssm_mod.SLSTMState(
            c=jnp.zeros((batch, di), jnp.float32),
            n=jnp.zeros((batch, di), jnp.float32),
            h=jnp.zeros((batch, di), jnp.float32),
            m=jnp.full((batch, di), -1e30, jnp.float32))
    raise ValueError(kind)


def init_block_pages(cfg: ModelConfig, kind: str, num_pages: int,
                     page_size: int, dtype):
    """Paged-serving cache for one block: KV page pools for attention
    kinds.  Recurrent kinds (mlstm/slstm/hymba) carry O(1) per-slot state
    -- nothing to page -- and are not yet wired into the paged engine."""
    if kind in ("attn", "attn_local", "moe"):
        return attn_mod.init_kv_pages(cfg, num_pages, page_size, dtype)
    raise NotImplementedError(
        f"paged serving supports attention-cache blocks only, got {kind!r}")


def block_cache_logical(cfg: ModelConfig, kind: str, batch: int,
                        max_seq: int):
    """Logical axes for every cache leaf (mirrors init_block_cache)."""
    if attn_mod.KV_CACHE_LAYOUT == "bhsd":
        kvshape = (batch, cfg.num_kv_heads, max_seq, cfg.head_dim)
        axes = ("batch", "heads", "kv_seq", None)
    else:
        kvshape = (batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
        axes = ("batch", "kv_seq", "heads", None)
    kv = attn_mod.KVCache(k=(axes, kvshape), v=(axes, kvshape))
    if kind in ("attn", "attn_local", "moe"):
        return kv
    if kind in ("hymba", "hymba_local"):
        di, n = cfg.q_dim, cfg.ssm_state_size
        return {
            "kv": kv,
            "mamba": ssm_mod.MambaState(
                h=(("batch", "channels", None), (batch, di, n)),
                conv=(("batch", None, "channels"),
                      (batch, cfg.conv_kernel - 1, di))),
        }
    if kind == "mlstm":
        di = int(cfg.d_model * cfg.mlstm_proj_factor)
        nh = cfg.num_heads
        hd = di // nh
        return ssm_mod.MLSTMState(
            c=(("batch", None, None, "channels"), (batch, nh, hd, hd)),
            n=(("batch", None, None), (batch, nh, hd)),
            m=(("batch", None), (batch, nh)),
            conv=((None,), (0,)))
    if kind == "slstm":
        di = int(cfg.d_model * cfg.mlstm_proj_factor)
        s2 = (("batch", "channels"), (batch, di))
        return ssm_mod.SLSTMState(c=s2, n=s2, h=s2, m=s2)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _attn_block_tail(params, x, a, cfg: ModelConfig, kind: str):
    """Residual + FFN half of an attention block -- shared by the train,
    dense-decode and paged-decode paths so they cannot diverge."""
    if cfg.post_norm:
        a = apply_norm(params["ln1_post"], a, cfg.norm_type, cfg.norm_eps)
    x = x + a
    h2 = apply_norm(params["ln2"], x, cfg.norm_type, cfg.norm_eps)
    if kind == "moe":
        f = moe_mod.apply_moe(params["moe"], h2, cfg)
    else:
        f = mlp_mod.apply_mlp(params["mlp"], h2, cfg.mlp_type)
    if cfg.post_norm:
        f = apply_norm(params["ln2_post"], f, cfg.norm_type, cfg.norm_eps)
    return x + f


def apply_block(params, x, cfg: ModelConfig, kind: str, *, positions,
                impl: Optional[str] = None):
    d = cfg.d_model
    h = apply_norm(params["ln1"], x, cfg.norm_type, cfg.norm_eps)
    if kind in ("attn", "attn_local", "moe"):
        a = attn_mod.apply_attention(
            params["attn"], h, cfg, positions=positions,
            window=_window(cfg, kind), impl=impl)
        x = _attn_block_tail(params, x, a, cfg, kind)
    elif kind in ("hymba", "hymba_local"):
        a = attn_mod.apply_attention(
            params["attn"], h, cfg, positions=positions,
            window=_window(cfg, kind), impl=impl)
        m = ssm_mod.apply_mamba(params["mamba"], h, cfg)
        fused = 0.5 * (
            apply_norm(params["norm_attn"], a, cfg.norm_type, cfg.norm_eps)
            + apply_norm(params["norm_mamba"], m, cfg.norm_type,
                         cfg.norm_eps))
        x = x + fused
        h2 = apply_norm(params["ln2"], x, cfg.norm_type, cfg.norm_eps)
        x = x + mlp_mod.apply_mlp(params["mlp"], h2, cfg.mlp_type)
    elif kind == "mlstm":
        x = x + ssm_mod.apply_mlstm(params["cell"], h, cfg, impl=impl
                                    if impl in ("pallas", "interpret")
                                    else "reference")
    elif kind == "slstm":
        x = x + ssm_mod.apply_slstm(params["cell"], h, cfg)
    else:
        raise ValueError(kind)
    return constrain(x, "batch", "seq", None)


# ---------------------------------------------------------------------------
# decode (one token, with cache)
# ---------------------------------------------------------------------------

def apply_block_prefill_paged(params, x, cfg: ModelConfig, kind: str,
                              cache, *, page_table, pos_start, n_valid,
                              layer=0, impl: Optional[str] = None):
    """Chunked paged prefill: one prompt chunk (B, S, D) through the full
    block forward, K/V scattered into the paged pools (at ``layer`` of
    layer-stacked pools).  Rows past ``n_valid`` are padding (their
    outputs are garbage, their K/V lands in scratch)."""
    if kind not in ("attn", "attn_local", "moe"):
        raise NotImplementedError(
            f"paged serving supports attention-cache blocks only, "
            f"got {kind!r}")
    h = apply_norm(params["ln1"], x, cfg.norm_type, cfg.norm_eps)
    a, cache = attn_mod.apply_attention_prefill_paged(
        params["attn"], h, cfg, cache, page_table=page_table,
        pos_start=pos_start, n_valid=n_valid, layer=layer,
        window=_window(cfg, kind), impl=impl)
    x = _attn_block_tail(params, x, a, cfg, kind)
    return constrain(x, "batch", "seq", None), cache


def apply_block_decode_paged(params, x, cfg: ModelConfig, kind: str,
                             cache, *, page_table, pos, layer=0,
                             impl: Optional[str] = None):
    """Paged one-token decode: like apply_block_decode but positions are
    per-sequence (B,) and the KV cache is a shared page pool (read and
    written at ``layer`` of layer-stacked pools)."""
    if kind not in ("attn", "attn_local", "moe"):
        raise NotImplementedError(
            f"paged serving supports attention-cache blocks only, "
            f"got {kind!r}")
    h = apply_norm(params["ln1"], x, cfg.norm_type, cfg.norm_eps)
    a, cache = attn_mod.apply_attention_decode_paged(
        params["attn"], h, cfg, cache, page_table=page_table, pos=pos,
        layer=layer, window=_window(cfg, kind), impl=impl)
    x = _attn_block_tail(params, x, a, cfg, kind)
    return constrain(x, "batch", None, None), cache


def apply_block_decode(params, x, cfg: ModelConfig, kind: str, cache, *,
                       pos, impl: Optional[str] = None):
    h = apply_norm(params["ln1"], x, cfg.norm_type, cfg.norm_eps)
    if kind in ("attn", "attn_local", "moe"):
        a, cache = attn_mod.apply_attention_decode(
            params["attn"], h, cfg, cache, pos=pos,
            window=_window(cfg, kind), impl=impl)
        x = _attn_block_tail(params, x, a, cfg, kind)
    elif kind in ("hymba", "hymba_local"):
        a, kv = attn_mod.apply_attention_decode(
            params["attn"], h, cfg, cache["kv"], pos=pos,
            window=_window(cfg, kind), impl=impl)
        m, mstate = ssm_mod.apply_mamba(params["mamba"], h, cfg,
                                        state=cache["mamba"], decode=True)
        cache = {"kv": kv, "mamba": mstate}
        fused = 0.5 * (
            apply_norm(params["norm_attn"], a, cfg.norm_type, cfg.norm_eps)
            + apply_norm(params["norm_mamba"], m, cfg.norm_type,
                         cfg.norm_eps))
        x = x + fused
        h2 = apply_norm(params["ln2"], x, cfg.norm_type, cfg.norm_eps)
        x = x + mlp_mod.apply_mlp(params["mlp"], h2, cfg.mlp_type)
    elif kind == "mlstm":
        y, cache = ssm_mod.apply_mlstm(params["cell"], h, cfg,
                                       state=cache, decode=True)
        x = x + y
    elif kind == "slstm":
        y, cache = ssm_mod.apply_slstm(params["cell"], h, cfg,
                                       state=cache, decode=True)
        x = x + y
    else:
        raise ValueError(kind)
    return constrain(x, "batch", None, None), cache
