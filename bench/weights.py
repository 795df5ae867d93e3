"""Random weights of a configuration, made from ``--seed``.

Each tensor has a key of its own, derived from the seed, its name and its
layer, so the benchmark can make the served model's tree in one jitted
call and the reference can make any single layer again on its own, with
the same values.  Each layer's tensors and their shapes are the model
family's (``layer_shapes(cfg, i)`` of ``bench/families/<reference>.py``),
and may differ from layer to layer.  A
name ending in ``norm`` is a norm scale of ones; every other tensor is
drawn in float32 with the configuration's ``initializer_range`` and
rounded to bfloat16, the type it is served in; the reference computes on
those rounded values in float32.
"""
from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp

from manifest import family_of

# the ids of the first families' tensors, frozen: a seed draws the same
# numbers for them as it always did.  Any other name takes ``leaf_id``'s
# hash of the name.
LEAF_ID = {"embed": 0, "lm_head": 1, "final_norm": 2, "q": 3, "k": 4,
           "v": 5, "o": 6, "gate": 7, "up": 8, "down": 9, "q_bias": 10,
           "k_bias": 11, "v_bias": 12, "input_norm": 13, "post_norm": 14}
SERVED = jnp.bfloat16


def leaf_id(name: str) -> int:
    """A tensor name's id: ``LEAF_ID``'s, else a fixed 31-bit hash of the
    name (CRC-32), stable across processes and versions."""
    if name in LEAF_ID:
        return LEAF_ID[name]
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def base_key(seed: int):
    """A key from a seed of any size (more than 32 bits too)."""
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def _leaf(cfg: dict, key, name: str, layer, shape):
    if name.endswith("norm"):
        return jnp.ones(shape, SERVED)
    k = jax.random.fold_in(jax.random.fold_in(key, leaf_id(name)), layer)
    return (jax.random.normal(k, shape, jnp.float32)
            * cfg["initializer_range"]).astype(SERVED)


def layer(cfg: dict, key, i, shapes=None) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors, named and shaped as ``shapes``; by default
    the family's ``layer_shapes(cfg, i)``, so ``i`` may be traced only
    where ``shapes`` is given."""
    if shapes is None:
        shapes = family_of(cfg).layer_shapes(cfg, i)
    return {n: _leaf(cfg, key, n, i, s) for n, s in shapes.items()}


def layers(cfg: dict, key, start: int, stop: int) -> Dict[str, jax.Array]:
    """Layers ``start .. stop-1`` stacked on a leading axis by one
    ``vmap``; they must all have the tensors of layer ``start``."""
    fam = family_of(cfg)
    shapes = fam.layer_shapes(cfg, start)
    if any(fam.layer_shapes(cfg, i) != shapes for i in range(start, stop)):
        raise ValueError(f"layers {start}..{stop - 1} differ in their "
                         "tensors")
    return jax.vmap(lambda i: layer(cfg, key, i, shapes))(
        jnp.arange(start, stop))


def embed(cfg: dict, key) -> jax.Array:
    return _leaf(cfg, key, "embed", 0,
                 (cfg["vocab_size"], cfg["hidden_size"]))


def lm_head(cfg: dict, key) -> jax.Array:
    """The (D, V) head matrix: the embedding transposed where the
    configuration ties them."""
    if cfg.get("tie_word_embeddings"):
        return embed(cfg, key).T
    return _leaf(cfg, key, "lm_head", 0,
                 (cfg["hidden_size"], cfg["vocab_size"]))


def final_norm(cfg: dict) -> jax.Array:
    return jnp.ones((cfg["hidden_size"],), SERVED)
