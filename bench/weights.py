"""Random weights of a dense GQA configuration, made from ``--seed``.

Each tensor has a key of its own, derived from the seed, its name and its
layer, so the benchmark can make the served model's tree in one jitted
call and the reference can make any single layer again on its own, with
the same values.  Weights are drawn in float32 and rounded to bfloat16,
the type they are served in; the reference computes on those rounded
values in float32.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

LEAVES = ("q", "k", "v", "o", "gate", "up", "down", "q_bias", "k_bias",
          "v_bias", "input_norm", "post_norm")
LEAF_ID = {n: i for i, n in enumerate(("embed", "lm_head", "final_norm")
                                      + LEAVES)}
SERVED = jnp.bfloat16


def base_key(seed: int):
    """A key from a seed of any size (more than 32 bits too)."""
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def layer_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    d, hq, hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    hd, f = d // hq, cfg["intermediate_size"]
    shapes = {"q": (d, hq * hd), "k": (d, hkv * hd), "v": (d, hkv * hd),
              "o": (hq * hd, d), "gate": (d, f), "up": (d, f),
              "down": (f, d), "input_norm": (d,), "post_norm": (d,)}
    if cfg.get("qkv_bias"):
        shapes.update(q_bias=(hq * hd,), k_bias=(hkv * hd,),
                      v_bias=(hkv * hd,))
    return shapes


def _leaf(cfg: dict, key, name: str, layer, shape):
    if name.endswith("norm"):
        return jnp.ones(shape, SERVED)
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_ID[name]), layer)
    return (jax.random.normal(k, shape, jnp.float32)
            * cfg["initializer_range"]).astype(SERVED)


def layer(cfg: dict, key, i) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors (``i`` may be traced)."""
    return {n: _leaf(cfg, key, n, i, s)
            for n, s in layer_shapes(cfg).items()}


def embed(cfg: dict, key) -> jax.Array:
    return _leaf(cfg, key, "embed", 0,
                 (cfg["vocab_size"], cfg["hidden_size"]))


def lm_head(cfg: dict, key) -> jax.Array:
    return _leaf(cfg, key, "lm_head", 0,
                 (cfg["hidden_size"], cfg["vocab_size"]))


def final_norm(cfg: dict) -> jax.Array:
    return jnp.ones((cfg["hidden_size"],), SERVED)
