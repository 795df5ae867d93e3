"""Pipeline parallelism (GPipe) over a mesh axis.

Layers are split into `n_stages` contiguous stages; stage s lives on the
mesh axis coordinate s.  Microbatches flow through a ppermute ring: at
schedule tick t, stage s processes microbatch t-s (the classic GPipe
schedule with (n_stages-1) bubble ticks on each side).

Used when ParallelConfig.pipeline_stages > 1, mapping the `pod` axis to
stages (DESIGN.md §4.1: memory-bound giants trade the pure-DP pod axis for
PP).  Forward-only building block exposed here; the train path wraps it
with jax.grad (XLA differentiates through ppermute).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_body(stage_params, x_micro, *, stage_fn: Callable,
                  axis: str = "stage"):
    """shard_map body.  stage_params: this stage's params (leading layer
    dim already sliced); x_micro: (n_micro, mb, ...) full input (only
    stage 0 reads it).  Returns (n_micro, mb, ...) outputs (valid on every
    device after the trailing psum)."""
    idx = jax.lax.axis_index(axis)
    n = jax.lax.axis_size(axis)
    n_micro = x_micro.shape[0]
    mb_shape = x_micro.shape[1:]

    carry = jnp.zeros(mb_shape, x_micro.dtype)
    out = jnp.zeros_like(x_micro)
    perm = [(i, (i + 1) % n) for i in range(n)]

    for t in range(n_micro + n - 1):
        mb_idx = t - idx                      # traced (idx is traced)
        feed = x_micro[jnp.clip(t, 0, n_micro - 1)]
        inp = jnp.where(idx == 0, feed, carry)
        active = (mb_idx >= 0) & (mb_idx < n_micro)
        y = stage_fn(stage_params, inp)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # last stage banks its finished microbatch
        bank = jnp.where((idx == n - 1) & active, y, jnp.zeros_like(y))
        out = jax.lax.dynamic_update_slice(
            out, bank[None],
            (jnp.clip(mb_idx, 0, n_micro - 1),) + (0,) * len(mb_shape))
        carry = jax.lax.ppermute(y, axis, perm)
    # everyone gets the last stage's outputs
    return jax.lax.psum(jnp.where(idx == n - 1, out, jnp.zeros_like(out)),
                        axis)


def make_pipeline(mesh, stage_fn: Callable, *, axis: str = "stage",
                  params_spec=P("stage"), x_spec=P()):
    """Build a jit-able pipelined forward.

    stage_fn(stage_params, x) applies ONE stage's layers.  Stage params
    must have a leading stage dimension sharded over `axis`.
    """
    body = functools.partial(pipeline_body, stage_fn=lambda p, x:
                             stage_fn(jax.tree.map(lambda a: a[0], p), x),
                             axis=axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: params_spec, params_spec)
                  if not isinstance(params_spec, P) else params_spec,
                  x_spec),
        out_specs=x_spec,
        check_vma=False)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
