"""Questions about long documents kept in the prefix cache: a backlog,
every request due at the start of the window.

Parameters (``kind: docqa``): ``documents`` (``count`` and a ``length``
lognormal: ``median``, ``sigma``, ``min``, ``max``; all ``count``
lengths are one block, so every seed has the same documents' lengths in
another order), ``requests``, ``block``, ``question`` and ``output``
lognormals, and ``sampled_every``, ``temperature`` and ``top_k`` as in
``gen_backlog``.  Tokens are distinct random ids.

A request's prompt is one document followed by a question of its own.
The documents are asked in turn, in an order drawn from the seed and
kept for the whole schedule, so each is asked many times in a window and
any ``count`` consecutive requests ask about different documents.  The
set-up traffic ``{"what": "documents"}`` is the documents themselves,
served before the window so the prefix cache holds them when it opens.
"""
from __future__ import annotations

import numpy as np

from lengths import lognormal, tokens


def _documents(mix: dict, cfg: dict, rng: np.random.Generator) -> list:
    docs = mix["documents"]
    lens = lognormal(docs["length"], docs["count"], docs["count"], rng)
    return [tokens(rng, n, cfg["vocab_size"]) for n in lens]


def generate(mix: dict, cfg: dict, seed: int) -> list:
    rng = np.random.default_rng([seed, 3])
    docs = _documents(mix, cfg, rng)
    order = rng.permutation(len(docs))
    n, block = mix["requests"], mix["block"]
    qlen = lognormal(mix["question"], n, block, rng)
    olen = lognormal(mix["output"], n, block, rng)
    every = mix.get("sampled_every", 0)
    out = []
    for i in range(n):
        sampled = bool(every) and i % every == every - 1
        doc = int(order[i % len(docs)])
        out.append({
            "due": 0.0,
            "prompt": np.concatenate(
                [docs[doc], tokens(rng, qlen[i], cfg["vocab_size"])]),
            "max_new_tokens": olen[i],
            "sampling": ({"temperature": mix["temperature"],
                          "top_k": mix["top_k"],
                          "seed": int(rng.integers(0, 2 ** 31))}
                         if sampled else None),
            "document": doc,
        })
    return out


def setup_prompts(mix: dict, cfg: dict, seed: int, spec: dict) -> list:
    """The documents (the first draws of ``generate``), served in set-up
    so the cache holds them when the window opens."""
    if spec["what"] != "documents":
        raise ValueError(f"unknown set-up {spec['what']!r}")
    return _documents(mix, cfg, np.random.default_rng([seed, 3]))
