"""Multi-turn chat sessions over shared system prompts, arriving open
loop.

Parameters (``kind: sessions``): ``rate`` (requests per second, Poisson,
gaps stratified in ``block``s), ``requests`` (length of the schedule,
past the window), ``sessions`` live at once (turns go round-robin over
them), ``system_prompts`` of ``system_len`` tokens shared by the
sessions in turn, ``turns`` after which a session is replaced,
``message`` and ``output`` lognormals, ``order_seed`` (the order of the
gaps and lengths; the run's seed where absent), ``sampled_every``,
``temperature`` and ``top_k``.  Session ``s`` starts at turn ``s % turns`` with a history
of earlier turns already written.

A turn's prompt is its system prompt, the session's history and a new
user message.  The history holds each earlier turn's message and a
seeded stand-in for its answer (as long as that turn's output), not the
served tokens, so the inputs depend on the seed alone; the prefix cache
can hold the system prompt and the earlier prompts.  A session whose next
prompt and answer would pass ``max_seq_len`` is replaced too.
"""
from __future__ import annotations

import numpy as np

from lengths import exponential_gaps, lognormal, tokens


def generate(mix: dict, cfg: dict, seed: int) -> list:
    rng = np.random.default_rng([seed, 2])
    vocab = cfg["vocab_size"]
    n, block = mix["requests"], mix["block"]
    max_ctx = mix["engine"]["max_seq_len"]
    turns = mix["turns"]
    systems = [tokens(rng, mix["system_len"], vocab)
               for _ in range(mix["system_prompts"])]
    order = np.random.default_rng([mix.get("order_seed", seed), 2])
    gaps = exponential_gaps(mix["rate"], n, block, order)
    msg = lognormal(mix["message"], n, block, order)
    olen = lognormal(mix["output"], n, block, order)
    # the histories of the sessions' earlier turns
    n_hist = sum(s % turns for s in range(mix["sessions"]))
    hmsg = lognormal(mix["message"], n_hist, block, order)
    hans = lognormal(mix["output"], n_hist, block, order)

    def fresh(s, turn):
        return {"system": s % len(systems), "turn": turn, "history": []}

    sessions = []
    h = 0
    for s in range(mix["sessions"]):
        sess = fresh(s, s % turns)
        for _ in range(sess["turn"]):
            sess["history"] += [tokens(rng, hmsg[h], vocab),
                                tokens(rng, hans[h], vocab)]
            h += 1
        sessions.append(sess)
    every = mix.get("sampled_every", 0)
    out, due = [], 0.0
    for i in range(n):
        due += gaps[i]
        s = i % len(sessions)
        sess = sessions[s]
        m = tokens(rng, msg[i], vocab)
        parts = [systems[sess["system"]], *sess["history"], m]
        if sess["turn"] >= turns \
                or sum(map(len, parts)) + olen[i] > max_ctx:
            sess = sessions[s] = fresh(s, 0)
            parts = [systems[sess["system"]], m]
        sampled = bool(every) and i % every == every - 1
        out.append({
            "due": due,
            "prompt": np.concatenate(parts),
            "max_new_tokens": olen[i],
            "sampling": ({"temperature": mix["temperature"],
                          "top_k": mix["top_k"],
                          "seed": int(rng.integers(0, 2 ** 31))}
                         if sampled else None),
            "session": s,
        })
        sess["history"] += [m, tokens(rng, olen[i], vocab)]
        sess["turn"] += 1
    return out


def setup_prompts(mix: dict, cfg: dict, seed: int, spec: dict) -> list:
    """The shared system prompts (the first draws of ``generate``), served
    in set-up so the cache holds them when the window opens."""
    if spec["what"] != "system_prompts":
        raise ValueError(f"unknown set-up {spec['what']!r}")
    rng = np.random.default_rng([seed, 2])
    return [tokens(rng, mix["system_len"], cfg["vocab_size"])
            for _ in range(mix["system_prompts"])]
