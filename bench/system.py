"""The system under test: the repository's ``EngineCore``, built for one
cell.

This is the one module of the benchmark that imports the program (from
``src/`` of the checkout).  It makes the program's model configuration
and lays the benchmark's own random weights (``weights.py``) into the
program's parameter tree in one jitted call, both as the configuration's
model family says (``bench/families/<reference>.py``).  It sets the
engine up from the mix's ``engine`` block (its page pools built under
jit, ``fill_pools``), and warms every program the cell's traffic can
launch through the engine's public ``add_request``/``step``.  Where the
mix asks for ``tp`` > 1, the weights are made replicated on the engine's
mesh and the pools sharded over it, each on its devices from the start.
"""
from __future__ import annotations

import os
import sys
from typing import Iterable, List

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

import weights as W
from manifest import family_of

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from repro.config import ModelConfig, ParallelConfig, ServeConfig  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving.core import EngineCore  # noqa: E402
from repro.serving.pressure import copy_pages  # noqa: E402
from repro.serving.scheduler import SamplingParams  # noqa: E402
from repro.sharding.tp import plan_tp  # noqa: E402

# the program's decode and prefill attention kernels, as the trace names
# their custom calls
DECODE_KERNEL = "paged_flash_decode_fwd"
PREFILL_KERNEL = "paged_prefill_fwd"


def model_config(cfg: dict) -> ModelConfig:
    return ModelConfig(**family_of(cfg).program(cfg))


def make_params(model, cfg: dict, seed: int, sharding=None):
    """The served weights for ``seed`` in the program's tree, made on the
    device by one jitted call (the family's ``params`` lays them out),
    every leaf with ``sharding`` where one is given.  Fails if the result
    does not have the program's tree, shapes and types."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    fam = family_of(cfg)

    def make(key):
        return fam.params(want, cfg,
                          lambda start, stop: W.layers(cfg, key, start, stop),
                          W.embed(cfg, key), W.lm_head(cfg, key),
                          W.final_norm(cfg))

    key = W.base_key(seed)     # an argument: one program for every seed
    got = jax.eval_shape(make, key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("the family's weights do not fit the program's "
                         "tree")
    if sharding is None:
        return jax.jit(make)(key)
    return jax.jit(make, out_shardings=sharding)(key)


def serve_config(engine: dict) -> ServeConfig:
    return ServeConfig(
        max_batch=engine["max_batch"], max_seq_len=engine["max_seq_len"],
        page_size=engine["page_size"], num_pages=engine["num_pages"],
        prefill_chunk=engine["prefill_chunk"],
        prefix_cache=engine.get("prefix_cache", True),
        preempt_policy=engine.get("preempt_policy", "auto"),
        paged_impl=engine.get("paged_impl", "auto"),
        tp=engine.get("tp", 1))


def tp_mesh(mcfg: ModelConfig, serve: ServeConfig):
    """The tensor-parallel engine's mesh, built as ``EngineCore`` builds
    its own (``plan_tp``, then ``make_mesh``); None where ``tp`` is 1."""
    if serve.tp <= 1:
        return None
    plan = plan_tp(mcfg, serve.tp, serve.page_size,
                   collectives=serve.tp_collectives,
                   ar_chunks=serve.tp_ar_chunks,
                   first_chunk_frac=serve.tp_first_chunk_frac)
    return make_mesh(plan.mesh_shape, plan.axes)


def fill_pools(core: EngineCore) -> None:
    """Materialise the page pools under jit, on a tensor-parallel
    engine's mesh as ``LM.paged_cache_sharding`` shards them, so each
    device makes only its shard.  The engine's own first step builds
    them eagerly, layer by layer and then stacked, which holds more than
    two pools at once: beside phi3-mini's weights an 80-page pool (4.03
    GB) does not fit that way on a 16 GB chip."""
    mgr = core.mgr

    def make():
        return core.model.init_paged_cache(mgr.num_pages, mgr.page_size)

    if core.tp_mesh is None:
        core.pools = jax.jit(make)()
    else:
        core.pools = jax.jit(make, out_shardings=core.model
                             .paged_cache_sharding(core.tp_mesh,
                                                   mgr.num_pages,
                                                   mgr.page_size))()


def build(cfg: dict, engine: dict, seed: int):
    """The engine for one cell.  On a tensor-parallel engine the weights
    are made replicated on its mesh, so ``EngineCore``'s own placement
    of them moves nothing."""
    mcfg = model_config(cfg)
    model = build_model(mcfg, ParallelConfig())
    serve = serve_config(engine)
    mesh = tp_mesh(mcfg, serve)
    params = make_params(model, cfg, seed, None if mesh is None
                         else NamedSharding(mesh, PartitionSpec()))
    core = EngineCore(model, params, mcfg, serve)
    fill_pools(core)
    return core


def prefill_widths(max_batch: int) -> List[int]:
    """Launch widths the chunked prefill pads to: powers of two up to
    ``max_batch``."""
    w, out = 1, []
    while w < max_batch:
        out.append(w)
        w *= 2
    return out + [max_batch]


def _serve(core: EngineCore, prompts, samplings) -> None:
    for prompt, sp in zip(prompts, samplings):
        core.add_request(prompt, sp)
    while core.has_work:
        core.step()


def warm(core: EngineCore, samplings: Iterable[dict], cow_pairs: int = 0,
         max_width: int = 0) -> None:
    """Run every program the window can launch once, through the
    engine's own ``add_request``/``step``: for each prefill width a wave
    of that many requests whose prompts fit one chunk together, so they
    prefill in one launch of that width and then decode at ``max_batch``
    (the widest wave also holds one request of each sampling of the mix,
    so the mixed-sampling decode path and the sampled first token run).
    Prompts are shorter than a page, so the prefix cache keeps nothing of
    them.  Then copy-on-write page copies of 1..``cow_pairs`` pairs on the
    scratch page."""
    serve, mgr = core.serve, core.mgr
    b, chunk = serve.max_batch, serve.prefill_chunk_tokens
    widths = prefill_widths(min(b, max_width or b))
    plen = max(1, min(serve.page_size - 1, chunk // widths[-1]))
    rng = np.random.default_rng(0)
    sampled = [SamplingParams(temperature=s["temperature"], top_k=s["top_k"],
                              seed=1, max_new_tokens=3) for s in samplings]
    for w in widths:
        sps = [SamplingParams(max_new_tokens=3) for _ in range(w)]
        if w == widths[-1] and w > 1:
            sps[1:1 + len(sampled)] = sampled[:w - 1]
        before = core.prefill_launches
        _serve(core, [rng.integers(0, core.cfg.vocab_size, plen,
                                   dtype=np.int32) for _ in range(w)], sps)
        if core.prefill_launches - before != 1:
            raise RuntimeError(
                f"warm-up: a wave of {w} prompts made "
                f"{core.prefill_launches - before} prefill launches, not "
                "one of that width")
    for n in range(1, cow_pairs + 1):
        core.pools = copy_pages(core.pools, [mgr.SCRATCH] * n,
                                [mgr.SCRATCH] * n)
    jax.block_until_ready(core.pools)


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
