"""Offline backlog: every request is due at the start of the window, and
there are more of them than the window can finish.

Parameters (``kind: backlog``): ``requests``, ``block``, ``prompt`` and
``output`` lognormals (``median``, ``sigma``, ``min``, ``max``), and
``sampled_every`` (0: all greedy; k: every k-th request samples at
``temperature`` with ``top_k``).  Prompts are distinct random tokens, so
no prefix is shared.
"""
from __future__ import annotations

import numpy as np

from lengths import lognormal, tokens


def generate(mix: dict, cfg: dict, seed: int) -> list:
    rng = np.random.default_rng([seed, 1])
    n, block = mix["requests"], mix["block"]
    plen = lognormal(mix["prompt"], n, block, rng)
    olen = lognormal(mix["output"], n, block, rng)
    every = mix.get("sampled_every", 0)
    out = []
    for i in range(n):
        sampled = bool(every) and i % every == every - 1
        out.append({
            "due": 0.0,
            "prompt": tokens(rng, plen[i], cfg["vocab_size"]),
            "max_new_tokens": olen[i],
            "sampling": ({"temperature": mix["temperature"],
                          "top_k": mix["top_k"],
                          "seed": int(rng.integers(0, 2 ** 31))}
                         if sampled else None),
        })
    return out
