"""The document-QA generator (``kind: docqa``) and a whole run of its
tiny cell on the CPU."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH, DOCQA, run_cell
from manifest import Manifest

CFG = {"vocab_size": 152064}
SEEDS = (1, 2 ** 31 + 5, 2 ** 40 + 3)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "tp4-longdoc.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def gen():
    return Manifest(os.path.dirname(BENCH)).generator("docqa")


def _lengths(gen, mix, seed):
    docs = gen.setup_prompts(mix, CFG, seed, {"what": "documents"})
    sched = gen.generate(mix, CFG, seed)
    return (sorted(map(len, docs)),
            sorted(len(r["prompt"]) - len(docs[r["document"]])
                   for r in sched),
            sorted(r["max_new_tokens"] for r in sched))


def test_every_seed_has_the_same_lengths(gen, mix):
    first = _lengths(gen, mix, SEEDS[0])
    assert len(first[0]) == mix["documents"]["count"]
    for seed in SEEDS[1:]:
        assert _lengths(gen, mix, seed) == first
    assert gen.generate(mix, CFG, SEEDS[0])[5]["prompt"].tolist() \
        != gen.generate(mix, CFG, SEEDS[1])[5]["prompt"].tolist()


@pytest.mark.parametrize("seed", SEEDS)
def test_prompts_are_documents_and_questions(gen, mix, seed):
    docs = gen.setup_prompts(mix, CFG, seed, {"what": "documents"})
    sched = gen.generate(mix, CFG, seed)
    assert len(sched) == mix["requests"]
    count = mix["documents"]["count"]
    q = mix["question"]
    for i, r in enumerate(sched):
        assert r["due"] == 0.0 and r["sampling"] is None
        doc = docs[r["document"]]
        assert np.array_equal(r["prompt"][:len(doc)], doc)
        assert q["min"] <= len(r["prompt"]) - len(doc) <= q["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] \
            <= mix["engine"]["max_seq_len"]
        # asked in turn: any ``count`` consecutive requests differ
        ahead = [s["document"] for s in sched[i:i + count]]
        assert len(set(ahead)) == len(ahead)
    with pytest.raises(ValueError):
        gen.setup_prompts(mix, CFG, seed, {"what": "system_prompts"})


def test_docqa_run_is_correct(checkout, monkeypatch):
    import run
    runs = []
    made = run.Run

    def keep(*args, **kw):
        runs.append(made(*args, **kw))
        return runs[-1]

    monkeypatch.setattr(run, "Run", keep)
    rc, res = run_cell(checkout, DOCQA, "--trace", "1")
    assert rc == 0 and res["correct"], res
    assert res["failed"] == 0 and res["attempted"] == 40
    # the documents come from the cache: only the questions and the
    # documents' last partial pages are prefilled in the window
    hit = Manifest(checkout).reader("prefix_hit_pct.tp4")(runs[-1])
    assert hit > 50
    rc, res = run_cell(checkout, DOCQA)
    assert rc == 0 and res["correct"], res
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}


def test_prefix_hit_pct_counts_what_the_engine_took_in():
    """Under a backlog most submitted requests are still queued when the
    window closes: the share is of the prompt tokens the engine took in
    (hits and prefilled tokens), not of those submitted."""
    import types
    read = Manifest(os.path.dirname(BENCH)).reader("prefix_hit_pct.tp4")
    run = types.SimpleNamespace(counters={
        "prefix_hit_tokens_total": 990, "engine_prefill_tokens_total": 10},
        recs={})
    assert read(run) == pytest.approx(99.0)
    run.counters = {}
    assert read(run) is None
