"""Whole runs of ``bench/run.py`` on the CPU at a tiny size, skipping
only the look for a chip: a sound run is correct, a run whose timed path
is broken underneath is not, a later change can add a configuration, a
mix, a metric and a model family as files alone, and without a chip the
command prints no result."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from conftest import BACKLOG, CHAT, DOCQA, ROOT, run_cell


def test_sound_runs_are_correct(checkout):
    rc, res = run_cell(checkout, BACKLOG)
    assert rc == 0 and res["correct"], res
    assert set(res["metrics"]) == {"output_tok_s", "itl_p99_ms", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] == 40
    assert list(res)[-1] == "check"
    rc, res = run_cell(checkout, CHAT, "--trace", "1")
    assert rc == 0 and res["correct"], res
    assert "prefix_hit_pct" in res["metrics"]
    assert res["metrics"]["prefix_hit_pct"]["value"] > 0


def _wrap_decode(core, fn):
    orig = core._paged_fns

    def fns():
        a, b, dec, d = orig()
        return a, b, fn(dec), d

    core._paged_fns = fns


def state_unchanged(core):
    """The decode step leaves the KV pool as it was."""
    def wrap(dec):
        def f(params, tok, pools, table, pos):
            logits, _ = dec(params, tok, jax.tree.map(jnp.copy, pools),
                            table, pos)
            return logits, pools
        return f
    _wrap_decode(core, wrap)


def half_batch(core):
    """The decode step computes the first half of its rows only; the
    rest attend through the scratch page."""
    def wrap(dec):
        def f(params, tok, pools, table, pos):
            half = table.shape[0] // 2
            return dec(params, tok, pools,
                       table.at[half:].set(core.mgr.SCRATCH), pos)
        return f
    _wrap_decode(core, wrap)


def token_altered(core):
    """One token id's logit raised where the decode step produces it."""
    def wrap(dec):
        def f(params, tok, pools, table, pos):
            logits, pools = dec(params, tok, pools, table, pos)
            return logits.at[:, 7].add(10.0), pools
        return f
    _wrap_decode(core, wrap)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(checkout, fault):
    rc, res = run_cell(checkout, BACKLOG, break_engine=fault)
    assert rc == 0 and res is not None
    assert res["correct"] is False, res["check"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered],
                         ids=lambda f: f.__name__)
def test_broken_docqa_path_is_not_correct(checkout, fault):
    """The same faults under the document-QA mix, whose requests decode
    after a prefix-cache hit on a document prefilled in set-up."""
    rc, res = run_cell(checkout, DOCQA, break_engine=fault)
    assert rc == 0 and res is not None
    assert res["correct"] is False, res["check"]


def test_refused_requests_count(checkout):
    """Every other request of the window refused at submission: each is
    attempted and failed, the run is not correct, and the first-token
    tail that reaches them reads infinite."""
    import contextlib
    import io

    import run

    def refuse_every_other(core):
        from repro.serving.faults import RequestRejected
        add, opened, n = core.add_request, core.reset_metrics_window, [0]

        def window():
            opened()
            n[0] = 1

        def add_request(prompt, sampling=None):
            if n[0]:
                n[0] += 1
                if n[0] % 2:
                    raise RequestRejected("refused by the test")
            return add(prompt, sampling)

        core.add_request, core.reset_metrics_window = add_request, window

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", CHAT, "--seed", "17", "--seconds", "2"],
                      root=checkout, require_chip=False, use_cache=False,
                      break_engine=refuse_every_other)
    lines = buf.getvalue().strip().splitlines()
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is False
    assert res["failed"] >= res["attempted"] // 2 - 1 > 0, res
    ttft = next(x for x in lines if x.startswith("ttft over"))
    assert ttft.endswith("p90 inf"), ttft


def test_new_config_mix_and_metric_are_files_only(checkout):
    b = os.path.join(checkout, "bench")
    with open(f"{b}/configs/tiny.json") as f:
        cfg = json.load(f)
    cfg.update(num_attention_heads=8, num_key_value_heads=4)
    with open(f"{b}/configs/tiny-mha.json", "w") as f:
        json.dump(cfg, f)
    with open(f"{b}/traffic/tiny-backlog.json") as f:
        mix = json.load(f)
    mix["prompt"]["median"] = 24
    with open(f"{b}/traffic/tiny-short.json", "w") as f:
        json.dump(mix, f)
    with open(f"{b}/metrics/requests_finished.py", "w") as f:
        f.write("def read(run):\n"
                "    return sum(r.finished for r in run.recs.values())\n")
    with open(f"{b}/checks/tiny-mha.short.json", "w") as f:
        json.dump({"max": {"logit_gap": 0.002}, "sample": {
            "min_tokens": 200, "max_requests": 8, "least_tokens": 8}}, f)
    path = os.path.join(checkout, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["configs"].append({"name": "tiny-mha", "source": "test",
                           "file": "bench/configs/tiny-mha.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny-mha.short", "config": "tiny-mha",
                             "traffic": "tiny-short", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"]:
        if BACKLOG in m.get("workloads", ()):
            m["workloads"].append("tiny-mha.short")
    man["per_layer"].append({
        "name": "requests_finished", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "scheduler",
        "moves": "output_tok_s", "workloads": ["tiny-mha.short"]})
    with open(path, "w") as f:
        json.dump(man, f)
    from manifest import Manifest
    assert Manifest(checkout).problems() == []
    rc, res = run_cell(checkout, "tiny-mha.short", "--trace", "1")
    assert rc == 0 and res["correct"], res
    assert res["metrics"]["requests_finished"]["value"] > 0


def _add_family_cell(checkout, family, source):
    """A family file, a tiny configuration naming it, and a backlog cell
    of that configuration with its check, added as files and entries."""
    b = os.path.join(checkout, "bench")
    with open(f"{b}/families/{family}.py", "w") as f:
        f.write(source)
    with open(f"{b}/configs/tiny.json") as f:
        cfg = json.load(f)
    with open(f"{b}/configs/tiny-{family}.json", "w") as f:
        json.dump(dict(cfg, reference=family), f)
    cell = f"tiny-{family}.backlog"
    with open(f"{b}/checks/{BACKLOG}.json") as f:
        check = json.load(f)
    with open(f"{b}/checks/{cell}.json", "w") as f:
        json.dump(check, f)
    path = os.path.join(checkout, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["configs"].append({"name": f"tiny-{family}", "source": "test",
                           "file": f"bench/configs/tiny-{family}.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": cell, "config": f"tiny-{family}",
                             "traffic": "tiny-backlog", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if BACKLOG in m.get("workloads", ()):
            m["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(man, f)
    return cell


def test_new_family_is_files_only(checkout, monkeypatch):
    """A family of its own (a copy of the dense one that names one more
    kernel) joins as files and entries: the manifest check passes, a
    traced run is correct and hands the family's kernel to the trace's
    reduction; a family whose layer drops the MLP's residual makes the
    same run not correct, so the reference is the family's; a
    configuration naming no family file is reported."""
    import trace_reduce
    from manifest import Manifest
    with open(os.path.join(checkout, "bench", "families",
                           "dense_gqa.py")) as f:
        dense = f.read()
    cell = _add_family_cell(checkout, "dense_gqa_k",
                            dense + '\nKERNELS = ("tiny_extra_kernel",)\n')
    assert Manifest(checkout).problems() == []
    asked = []
    reduce_file = trace_reduce.reduce_file

    def spy(path, kernels=()):
        asked.append(tuple(kernels))
        return reduce_file(path, kernels)

    monkeypatch.setattr(trace_reduce, "reduce_file", spy)
    rc, res = run_cell(checkout, cell, "--trace", "1")
    assert rc == 0 and res["correct"], res
    assert asked and asked[-1][-1] == "tiny_extra_kernel", asked

    residual = "    return x + (jax.nn.silu(h @ w[\"gate\"])"
    assert residual in dense
    broken = _add_family_cell(checkout, "dense_gqa_no_mlp", dense.replace(
        residual, "    return x + 0 * (jax.nn.silu(h @ w[\"gate\"])"))
    rc, res = run_cell(checkout, broken)
    assert rc == 0 and res["correct"] is False, res["check"]

    b = os.path.join(checkout, "bench")
    with open(f"{b}/configs/tiny-dense_gqa_k.json") as f:
        cfg = json.load(f)
    with open(f"{b}/configs/tiny-dense_gqa_k.json", "w") as f:
        json.dump(dict(cfg, reference="dense_gqa_missing"), f)
    assert any("reference 'dense_gqa_missing' names no file" in p
               for p in Manifest(checkout).problems())


def test_no_chip_no_result(tmp_path):
    """Here JAX finds only the CPU: the command exits 2 and prints no
    result, also in a checkout holding nothing but the benchmark."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for root in (ROOT, str(tmp_path)):
        if root != ROOT:
            import shutil
            shutil.copytree(os.path.join(ROOT, "bench"),
                            os.path.join(root, "bench"),
                            ignore=shutil.ignore_patterns("__pycache__"))
            shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload",
             "phi3-mini-3.8b.offline-decode", "--seed", "1", "--seconds",
             "1", "--trace", "0"], cwd=root, env=env, capture_output=True,
            text=True, timeout=120)
        assert p.returncode != 0
        assert p.stdout.strip() == ""
