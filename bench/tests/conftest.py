"""Shared fixtures of the benchmark's CPU tests: ``bench/`` on the path,
and a throwaway checkout holding a copy of ``bench/`` plus a tiny
configuration and three tiny mixes, so a whole run fits on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

BACKLOG = "tiny.backlog"
CHAT = "tiny.chat"
DOCQA = "tiny.docqa"
# the tiny cell that stands in for each real cell, by its mix's kind
TINY_CELL = {"backlog": BACKLOG, "sessions": CHAT, "docqa": DOCQA}
DOCQA_REPORTS = ("output_tok_s", "device_idle_pct")


def with_family(cfg: dict) -> dict:
    """A configuration as ``Manifest.config`` gives it: with the
    ``family_file`` its ``reference`` names, here in this ``bench/``."""
    return dict(cfg, family_file=os.path.join(
        BENCH, "families", f"{cfg['reference']}.py"))


TINY = with_family({
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "intermediate_size": 128, "vocab_size": 512,
    "qkv_bias": True, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "initializer_range": 0.02, "tie_word_embeddings": False,
    "reference": "dense_gqa"})
ENGINE = {"max_batch": 4, "max_seq_len": 256, "page_size": 16,
          "num_pages": 40, "prefill_chunk": 32, "prefix_cache": True,
          "preempt_policy": "recompute"}


DOCQA_MIX = {"kind": "docqa",
             "documents": {"count": 4, "length": {
                 "median": 96, "sigma": 0.4, "min": 48, "max": 160}},
             "requests": 40, "block": 8,
             "question": {"median": 12, "sigma": 0.6, "min": 4,
                          "max": 40},
             "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
             "sampled_every": 0,
             "setup_requests": [{"what": "documents",
                                 "max_new_tokens": 1}],
             "engine": dict(ENGINE, num_pages=64)}


def make_checkout(root: str) -> str:
    """A checkout at ``root``: ``bench/`` copied, the tiny files added and
    a BENCHMARK.json whose cells are the tiny ones (the real metrics,
    with their ``workloads`` moved onto the tiny cell of the same kind of
    mix)."""
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = os.path.join(root, "bench")
    with open(f"{b}/configs/tiny.json", "w") as f:
        json.dump({k: v for k, v in TINY.items() if k != "family_file"}, f)
    with open(f"{b}/traffic/tiny-backlog.json", "w") as f:
        json.dump({"kind": "backlog", "requests": 40, "block": 8,
                   "prompt": {"median": 40, "sigma": 0.5, "min": 16,
                              "max": 120},
                   "output": {"median": 12, "sigma": 0.5, "min": 4,
                              "max": 40},
                   "sampled_every": 0, "engine": ENGINE}, f)
    with open(f"{b}/traffic/tiny-chat.json", "w") as f:
        json.dump({"kind": "sessions", "rate": 20.0, "requests": 200,
                   "block": 8, "sessions": 8, "system_prompts": 2,
                   "system_len": 32, "turns": 3,
                   "message": {"median": 12, "sigma": 0.6, "min": 4,
                               "max": 40},
                   "output": {"median": 8, "sigma": 0.5, "min": 3,
                              "max": 20},
                   "sampled_every": 4, "temperature": 0.8, "top_k": 8,
                   "drain": "first_token",
                   "setup_requests": [{"what": "system_prompts",
                                       "max_new_tokens": 1}],
                   "engine": dict(ENGINE, cow_pairs=2)}, f)
    with open(f"{b}/traffic/tiny-docqa.json", "w") as f:
        json.dump(DOCQA_MIX, f)
    for c in (BACKLOG, CHAT, DOCQA):
        with open(f"{b}/checks/{c}.json", "w") as f:
            json.dump({"max": {"logit_gap": 0.002},
                       "sample": {"min_tokens": 200, "max_requests": 8,
                                  "least_tokens": 8}}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    real = {}
    for w in man["workloads"]:
        with open(os.path.join(BENCH, "traffic", f"{w['traffic']}.json")) as f:
            real[w["name"]] = TINY_CELL[json.load(f)["kind"]]
    man["configs"] = [{"name": "tiny", "source": "test",
                       "file": "bench/configs/tiny.json", "reduced": [],
                       "why": "test"}]
    man["workloads"] = [
        {"name": BACKLOG, "config": "tiny", "traffic": "tiny-backlog",
         "chips": 1, "why": "test"},
        {"name": CHAT, "config": "tiny", "traffic": "tiny-chat",
         "chips": 1, "why": "test"},
        {"name": DOCQA, "config": "tiny", "traffic": "tiny-docqa",
         "chips": 1, "why": "test"}]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({real[w] for w in m["workloads"]})
        if DOCQA not in real.values() and m["name"] in DOCQA_REPORTS:
            # no real docqa cell yet: the tiny one reports what the
            # four-chip document-QA cell is to report
            m["workloads"].append(DOCQA)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


@pytest.fixture
def checkout(tmp_path):
    return make_checkout(str(tmp_path))


def run_cell(root, cell, *extra, seconds="2", seed="3000000001",
             break_engine=None):
    """``bench/run.py`` in-process on the CPU; returns (exit code, the
    last line of standard output as JSON or None)."""
    import contextlib
    import io

    import run
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", cell, "--seed", seed, "--seconds",
                       seconds, *extra], root=root, require_chip=False,
                      use_cache=False, break_engine=break_engine)
    lines = buf.getvalue().strip().splitlines()
    try:
        return rc, json.loads(lines[-1])
    except (IndexError, ValueError):
        return rc, None
