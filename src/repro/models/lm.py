"""Decoder-only language model covering the dense / moe / ssm / hybrid /
vlm families.

The per-layer block pattern (config.blocks()) is compressed into *segments*
of consecutive identical kinds; each multi-block segment is executed with
``lax.scan`` over layer-stacked parameters (small HLO, fast compiles at 512
devices) with configurable rematerialization.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ParallelConfig
from repro.layers import common
from repro.layers.embedding import (embed_tokens, embedding_logical,
                                    init_embedding, lm_logits)
from repro.layers.norms import apply_norm, init_norm, norm_logical
from repro.models import blocks as B
from repro.sharding.rules import constrain


def segments(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """Compress the block pattern into (kind, count) runs."""
    segs: List[Tuple[str, int]] = []
    for kind in cfg.blocks():
        if segs and segs[-1][0] == kind:
            segs[-1] = (kind, segs[-1][1] + 1)
        else:
            segs.append((kind, 1))
    return segs


def periodic_segments(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """Detect a repeating *unit* (e.g. gemma2's (local, global)) so that
    alternating patterns still scan.  Returns [(unit_kinds, repeats)]."""
    blocks = cfg.blocks()
    n = len(blocks)
    for p in (1, 2, 3, 4):
        if n % p == 0 and len(set(blocks[i::p][0] for i in range(p))) >= 0:
            unit = blocks[:p]
            if all(blocks[i] == unit[i % p] for i in range(n)):
                return [(tuple(unit), n // p)]
    # fall back to plain runs
    return [((k,), c) for k, c in segments(cfg)]


@dataclass
class LM:
    cfg: ModelConfig
    parallel: ParallelConfig = field(default_factory=ParallelConfig)

    # ------------------------------------------------------------------
    @property
    def segs(self):
        if self.parallel.scan_layers:
            return periodic_segments(self.cfg)
        return [((k,), 1) for k in self.cfg.blocks()]

    def init(self, key) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        keys = jax.random.split(key, len(self.segs) + 2)
        params: dict = {"embedding": init_embedding(keys[0], cfg, dtype),
                        "final_norm": init_norm(cfg.d_model, cfg.norm_type,
                                                dtype)}
        if cfg.modality != "text":
            from repro.layers.frontend import init_frontend
            params["frontend"] = init_frontend(keys[1], cfg, dtype)
        for si, (unit, reps) in enumerate(self.segs):
            kseg = jax.random.split(keys[si + 2], reps)

            def init_unit(k):
                ku = jax.random.split(k, len(unit))
                return {f"u{i}": B.init_block(ku[i], cfg, unit[i], dtype)
                        for i in range(len(unit))}

            if reps == 1:
                params[f"seg{si}"] = init_unit(kseg[0])
            else:
                # vmapped over the layer keys: the same values as
                # stacking per-layer inits, built stacked (one copy, one
                # small program instead of one per layer)
                params[f"seg{si}"] = jax.vmap(init_unit)(kseg)
        return params

    def logical(self) -> dict:
        cfg = self.cfg
        tree: dict = {"embedding": embedding_logical(cfg),
                      "final_norm": norm_logical(cfg.d_model, cfg.norm_type)}
        if cfg.modality != "text":
            from repro.layers.frontend import frontend_logical
            tree["frontend"] = frontend_logical(cfg)
        for si, (unit, reps) in enumerate(self.segs):
            unit_tree = {f"u{i}": B.block_logical(cfg, unit[i])
                         for i in range(len(unit))}
            if reps > 1:
                unit_tree = common.stack_logical(unit_tree)
            tree[f"seg{si}"] = unit_tree
        return tree

    # ------------------------------------------------------------------
    def _unit_fn(self, unit, *, positions, impl=None):
        cfg = self.cfg

        def run(x, unit_params):
            for i, kind in enumerate(unit):
                x = B.apply_block(unit_params[f"u{i}"], x, cfg, kind,
                                  positions=positions, impl=impl)
            return x

        if self.parallel.remat == "full":
            run = jax.checkpoint(run)
        elif self.parallel.remat == "selective":
            run = jax.checkpoint(
                run, policy=jax.checkpoint_policies.
                dots_with_no_batch_dims_saveable)
        return run

    def hidden_states(self, params, x, *, positions, impl=None):
        """Backbone forward: embedded input -> final-norm hidden states."""
        for si, (unit, reps) in enumerate(self.segs):
            run = self._unit_fn(unit, positions=positions, impl=impl)
            p = params[f"seg{si}"]
            if reps == 1:
                x = run(x, p)
            else:
                x, _ = jax.lax.scan(
                    lambda c, pp: (run(c, pp), None), x, p)
        return apply_norm(params["final_norm"], x, self.cfg.norm_type,
                          self.cfg.norm_eps)

    def apply(self, params, tokens=None, *, inputs_embeds=None,
              positions=None, impl=None):
        """Forward to logits.  tokens: (B, S) int32 or inputs_embeds
        (B, S, D) for the vlm/audio stubs."""
        cfg = self.cfg
        if inputs_embeds is not None:
            x = inputs_embeds.astype(jnp.dtype(cfg.dtype))
            if "frontend" in params:
                from repro.layers.frontend import apply_frontend
                x = apply_frontend(params["frontend"], x, cfg)
        else:
            x = embed_tokens(params["embedding"], tokens, cfg)
        if positions is None:
            b, s = x.shape[:2]
            pos2d = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                     (b, s))
            positions = (jnp.broadcast_to(pos2d, (3, b, s))
                         if cfg.rope_type == "mrope" else pos2d)
        x = self.hidden_states(params, x, positions=positions, impl=impl)
        return lm_logits(params["embedding"] if cfg.tie_embeddings
                         else params["embedding"], x, cfg)

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        cache = {}
        for si, (unit, reps) in enumerate(self.segs):
            def unit_cache():
                return {f"u{i}": B.init_block_cache(cfg, unit[i], batch,
                                                    max_seq, dtype)
                        for i in range(len(unit))}
            if reps == 1:
                cache[f"seg{si}"] = unit_cache()
            else:
                cache[f"seg{si}"] = common.stack_params(
                    [unit_cache() for _ in range(reps)])
        return cache

    def init_paged_cache(self, num_pages: int, page_size: int):
        """Paged-serving cache: per-layer KV page pools (no batch dim --
        serving/paged_cache.PagedKVCache owns the page table that carves
        the pools into per-sequence caches)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        cache = {}
        for si, (unit, reps) in enumerate(self.segs):
            def unit_cache():
                return {f"u{i}": B.init_block_pages(
                            cfg, unit[i], num_pages, page_size, dtype)
                        for i in range(len(unit))}
            if reps == 1:
                cache[f"seg{si}"] = unit_cache()
            else:
                cache[f"seg{si}"] = common.stack_params(
                    [unit_cache() for _ in range(reps)])
        return cache

    def paged_cache_sharding(self, mesh, num_pages: int, page_size: int):
        """NamedSharding tree for ``init_paged_cache`` under the paged-TP
        mesh: pool leaves (Hkv, P, ps, D) shard kv heads over the
        head-group axis and within-page rows over the page-row axis
        (logical axes ``kv_heads`` / ``page_row`` in sharding/rules.py);
        scan-stacked segments carry a leading replicated reps dim.  Axes
        whose dimension does not divide fall back to replicated, so SSM
        state leaves (if any) stay whole."""
        from jax.sharding import NamedSharding
        from repro.sharding.rules import (default_rules, logical_to_spec,
                                          _divides)
        rules = dict(default_rules())
        shapes = jax.eval_shape(
            lambda: self.init_paged_cache(num_pages, page_size))

        def to_sharding(leaf):
            logical = ("kv_heads", None, "page_row", None)
            if leaf.ndim == 5:          # stacked segment: leading reps dim
                logical = (None,) + logical
            elif leaf.ndim != 4:
                return NamedSharding(mesh, jax.sharding.PartitionSpec())
            spec = logical_to_spec(logical, rules, mesh)
            fixed = [ax if _divides(leaf.shape[i], mesh, ax) else None
                     for i, ax in enumerate(spec)]
            return NamedSharding(mesh,
                                 jax.sharding.PartitionSpec(*fixed))

        return jax.tree.map(to_sharding, shapes)

    def cache_logical(self, batch: int, max_seq: int):
        cfg = self.cfg
        tree = {}
        for si, (unit, reps) in enumerate(self.segs):
            unit_tree = {f"u{i}": B.block_cache_logical(cfg, unit[i], batch,
                                                        max_seq)
                         for i in range(len(unit))}
            if reps > 1:
                unit_tree = common.stack_logical(unit_tree)
            tree[f"seg{si}"] = unit_tree
        return tree

    def _cached_segments(self, params, x, cache, block_fn):
        """Shared cached-forward skeleton: thread (x, cache) through every
        segment (scanning stacked units), final-norm and project to
        logits.  ``block_fn(block_params, x, kind, block_cache) ->
        (x, new_block_cache)`` supplies the per-block forward (the dense
        one-token decode; paged serving runs ``_paged_segments``).
        x: (B, S, D) embedded input; returns (logits (B, S, V), cache)."""
        cfg = self.cfg
        new_cache = {}
        for si, (unit, reps) in enumerate(self.segs):

            def run(x, unit_params, unit_cache):
                ncache = {}
                for i, kind in enumerate(unit):
                    x, c = block_fn(unit_params[f"u{i}"], x, kind,
                                    unit_cache[f"u{i}"])
                    ncache[f"u{i}"] = c
                return x, ncache

            p, c = params[f"seg{si}"], cache[f"seg{si}"]
            if reps == 1:
                x, nc = run(x, p, c)
            else:
                def body(carry, pc):
                    pp, cc = pc
                    y, nc = run(carry, pp, cc)
                    return y, nc
                x, nc = jax.lax.scan(body, x, (p, c))
            new_cache[f"seg{si}"] = nc
        x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        logits = lm_logits(params["embedding"], x, cfg)
        return logits, new_cache

    def _paged_segments(self, params, x, pools, block_fn):
        """Paged-serving skeleton: like ``_cached_segments``, but a
        stacked segment carries its layer-stacked pools (L, Hkv, P, ps,
        D) through the layer scan instead of slicing them out as ``xs``
        and restacking them as ``ys``.  ``block_fn(block_params, x, kind,
        block_pools, layer) -> (x, new_block_pools)`` writes and reads
        layer ``layer`` of the stacked pools in place, so the donated pool
        buffer is never copied; a one-layer segment passes its (Hkv, P,
        ps, D) pools with layer 0.  Returns (logits (B, S, V), pools)."""
        cfg = self.cfg
        new_pools = {}
        for si, (unit, reps) in enumerate(self.segs):

            def run(x, unit_params, unit_pools, layer):
                unit_pools = dict(unit_pools)
                for i, kind in enumerate(unit):
                    x, unit_pools[f"u{i}"] = block_fn(
                        unit_params[f"u{i}"], x, kind, unit_pools[f"u{i}"],
                        layer)
                return x, unit_pools

            p, c = params[f"seg{si}"], pools[f"seg{si}"]
            if reps == 1:
                x, c = run(x, p, c, 0)
            else:
                def body(carry, xs):
                    (x, c), (pp, layer) = carry, xs
                    return run(x, pp, c, layer), None
                (x, c), _ = jax.lax.scan(
                    body, (x, c), (p, jnp.arange(reps, dtype=jnp.int32)))
            new_pools[f"seg{si}"] = c
        x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        logits = lm_logits(params["embedding"], x, cfg)
        return logits, new_pools

    def decode_step(self, params, token, cache, pos, *, impl=None):
        """token: (B,) int32; pos: scalar int32.  Returns (logits, cache)."""
        def block_fn(bp, x, kind, bc):
            return B.apply_block_decode(bp, x, self.cfg, kind, bc, pos=pos,
                                        impl=impl)
        x = embed_tokens(params["embedding"], token[:, None], self.cfg)
        logits, cache = self._cached_segments(params, x, cache, block_fn)
        return logits[:, 0], cache

    def decode_step_paged(self, params, token, cache, page_table, pos, *,
                          impl=None):
        """Paged decode step.  token: (B,) int32; pos: (B,) int32
        per-sequence positions (ragged batch); page_table: (B, n_kv)
        int32.  Returns (logits, cache) with cache = the page pools."""
        def block_fn(bp, x, kind, bc, layer):
            return B.apply_block_decode_paged(
                bp, x, self.cfg, kind, bc, page_table=page_table, pos=pos,
                layer=layer, impl=impl)
        x = embed_tokens(params["embedding"], token[:, None], self.cfg)
        logits, cache = self._paged_segments(params, x, cache, block_fn)
        return logits[:, 0], cache

    def prefill_chunk_paged(self, params, tokens, cache, page_table,
                            pos_start, n_valid, *, impl=None):
        """Chunked paged prefill: one fixed-size prompt chunk through the
        full transformer forward, writing K/V into the paged pools.

        tokens: (B, C) int32 chunk (padded past ``n_valid``); page_table:
        (B, n_kv) int32; pos_start / n_valid: (B,) int32 runtime offsets
        -- jit traces are keyed by the chunk size C, never by prompt
        length or chunk position.  Returns (logits (B, C, V), cache);
        logit rows past ``n_valid`` are garbage (their K/V is not
        written).
        """
        def block_fn(bp, x, kind, bc, layer):
            return B.apply_block_prefill_paged(
                bp, x, self.cfg, kind, bc, page_table=page_table,
                pos_start=pos_start, n_valid=n_valid, layer=layer,
                impl=impl)
        x = embed_tokens(params["embedding"], tokens, self.cfg)
        return self._paged_segments(params, x, cache, block_fn)

    # ------------------------------------------------------------------
    def loss(self, params, tokens, labels, *, impl=None):
        """Mean next-token cross entropy; labels < 0 are masked."""
        logits = self.apply(params, tokens, impl=impl).astype(jnp.float32)
        mask = labels >= 0
        lab = jnp.maximum(labels, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)
