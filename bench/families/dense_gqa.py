"""Dense GQA transformer (Phi-3, Qwen2): per layer RMSNorm, Q/K/V
projections (with biases where the configuration has ``qkv_bias``),
rotary position embedding on the two halves of each head, causal softmax
attention with each KV head shared by ``Hq / Hkv`` query heads, the
output projection and a residual, then RMSNorm, a SwiGLU MLP and a
residual.  The head size is the configuration's ``head_dim``, or
``hidden_size / num_attention_heads`` where it has none.

The interface a family module fulfils is in ``README.md`` beside it.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

import flops
import reference as R

# the matrices the int8 control quantises
MATRICES = ("q", "k", "v", "o", "gate", "up", "down")

# program parameter leaf (below the stacked layer segment) -> layer tensor
LAYOUT = {("attn", "wq"): "q", ("attn", "wk"): "k", ("attn", "wv"): "v",
          ("attn", "wo"): "o", ("attn", "bq"): "q_bias",
          ("attn", "bk"): "k_bias", ("attn", "bv"): "v_bias",
          ("mlp", "w_gate"): "gate", ("mlp", "w_up"): "up",
          ("mlp", "w_down"): "down", ("ln1", "scale"): "input_norm",
          ("ln2", "scale"): "post_norm"}


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim",
                   cfg["hidden_size"] // cfg["num_attention_heads"])


def program(cfg: dict) -> dict:
    return dict(
        name=cfg.get("model_type", "dense"), family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        block_pattern=("attn",), qkv_bias=bool(cfg.get("qkv_bias")),
        rope_theta=float(cfg["rope_theta"]), norm_type="rmsnorm",
        norm_eps=float(cfg["rms_norm_eps"]), mlp_type="swiglu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype="bfloat16", param_dtype="bfloat16")


def params(want, cfg: dict, layers, embed, head, norm):
    """Every layer in one stacked segment ``seg0/u0``; refuses any other
    program tree."""
    if set(want) != {"embedding", "final_norm", "seg0"} \
            or set(want["seg0"]) != {"u0"}:
        raise ValueError(f"unexpected parameter tree {sorted(want)}")
    stack = layers(0, cfg["num_hidden_layers"])
    unit = {}
    for (grp, leaf), name in LAYOUT.items():
        if leaf in want["seg0"]["u0"].get(grp, {}):
            unit.setdefault(grp, {})[leaf] = stack[name]
    emb = {"embed": embed}
    if "lm_head" in want["embedding"]:
        emb["lm_head"] = head
    return {"embedding": emb, "final_norm": {"scale": norm},
            "seg0": {"u0": unit}}


def layer_shapes(cfg: dict, i: int) -> Dict[str, Tuple[int, ...]]:
    """Every layer alike."""
    m = dims(cfg)
    d, f, qd, kvd = m["D"], m["F"], m["Hq"] * m["hd"], m["Hkv"] * m["hd"]
    shapes = {"q": (d, qd), "k": (d, kvd), "v": (d, kvd), "o": (qd, d),
              "gate": (d, f), "up": (d, f), "down": (f, d),
              "input_norm": (d,), "post_norm": (d,)}
    if m["bias"]:
        shapes.update(q_bias=(qd,), k_bias=(kvd,), v_bias=(kvd,))
    return shapes


def block(cfg: dict, w: Dict[str, jax.Array], x, n_valid, i):
    """One layer over one padded sequence x: (S, D); rows past
    ``n_valid`` are padding (causality keeps them out of real rows).
    Every layer alike, so the index ``i`` is not read."""
    s = x.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = head_dim(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(s)
    h = R.rms(x, w["input_norm"], eps)
    q, k, v = h @ w["q"], h @ w["k"], h @ w["v"]
    if "q_bias" in w:
        q, k, v = q + w["q_bias"], k + w["k_bias"], v + w["v_bias"]
    q = R.rope(q.reshape(s, hq, hd), pos, theta)
    k = R.rope(k.reshape(s, hkv, hd), pos, theta)
    x = x + R.attention(q, k, v.reshape(s, hkv, hd)) @ w["o"]
    h = R.rms(x, w["post_norm"], eps)
    return x + (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]


def dims(cfg: dict) -> dict:
    return {"L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
            "Hq": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "hd": head_dim(cfg),
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "bias": bool(cfg.get("qkv_bias"))}


def layer_matmul_params(cfg: dict, i: int) -> int:
    """Weights one token multiplies in layer ``i`` (every layer alike):
    Q, K, V, O and the three SwiGLU projections."""
    m = dims(cfg)
    qd, kvd = m["Hq"] * m["hd"], m["Hkv"] * m["hd"]
    return m["D"] * qd + 2 * m["D"] * kvd + qd * m["D"] + 3 * m["D"] * m["F"]


def param_count(cfg: dict) -> int:
    """All parameters: layers (with norms and biases), the embedding, the
    final norm and the untied head."""
    m = dims(cfg)
    per = layer_matmul_params(cfg, 0) + 2 * m["D"]
    if m["bias"]:
        per += (m["Hq"] + 2 * m["Hkv"]) * m["hd"]
    head = 0 if cfg.get("tie_word_embeddings") else m["V"] * m["D"]
    return m["L"] * per + m["V"] * m["D"] + head + m["D"]


def kv_bytes_per_token(cfg: dict) -> int:
    m = dims(cfg)
    return 2 * m["L"] * m["Hkv"] * m["hd"] * flops.BF16
