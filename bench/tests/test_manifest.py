"""The manifest check on BENCHMARK.json, and on copies of it broken in
each way the check has to catch."""
import copy
import json
import os

import pytest

from conftest import ROOT, make_checkout
from manifest import Manifest


def test_benchmark_json_passes():
    assert Manifest(ROOT).problems() == []


def _broken(tmp_path, edit):
    root = make_checkout(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    assert Manifest(root).problems() == []
    edit(data, root)
    with open(path, "w") as f:
        json.dump(data, f)
    return Manifest(root).problems()


def _four_chips(d, root):
    for w in d["workloads"]:
        w["chips"] = 4


def _listed_where_not_moved(d, root):
    # kv_peak_pct moves output_tok_s, which the chat cell lacks
    m = next(m for m in d["per_layer"] if m["name"] == "kv_peak_pct")
    m["workloads"] = [w["name"] for w in d["workloads"]]


def _missing(kind):
    def edit(d, root):
        b = os.path.join(root, "bench")
        os.remove({"config": f"{b}/configs/tiny.json",
                   "traffic": f"{b}/traffic/tiny-chat.json",
                   "metric": f"{b}/metrics/kv_peak_pct.py"}[kind])
    return edit


def _reference(name):
    def edit(d, root):
        path = os.path.join(root, "bench", "configs", "tiny.json")
        with open(path) as f:
            cfg = json.load(f)
        with open(path, "w") as f:
            json.dump(dict(cfg, reference=name), f)
    return edit


def _family_without_block(d, root):
    fams = os.path.join(root, "bench", "families")
    with open(os.path.join(fams, "dense_gqa.py")) as f:
        src = f.read()
    with open(os.path.join(fams, "no_block.py"), "w") as f:
        f.write(src.replace("def block(", "def not_block("))
    _reference("no_block")(d, root)


@pytest.mark.parametrize("edit,needle", [
    (lambda d, r: d["workloads"][0].update(name="bad name"), "bad name"),
    (lambda d, r: d["per_layer"][0].update(name="a,b"), "bad name"),
    (lambda d, r: d["end_to_end"][0].update(unit="tokens per s"),
     "bad unit"),
    (lambda d, r: d["per_layer"][0].update(unit="µs"), "bad unit"),
    (_listed_where_not_moved, "does not report"),
    (_four_chips, "ask for 4 chips"),
    (_missing("config"), "missing bench/configs/tiny.json"),
    (_missing("traffic"), "missing traffic file"),
    (_missing("metric"), "no reader file"),
    (lambda d, r: d["configs"][0].update(reduced=["hidden_size"]),
     "reduced"),
    (lambda d, r: d["end_to_end"][0].update(bound=0.3), "bound"),
    (lambda d, r: d["workloads"][0].update(extra=1), "keys"),
    (_reference("moe_unknown"), "names no file under bench/families/"),
    (_reference("../configs/tiny"), "names no file under bench/families/"),
    (_family_without_block, "family no_block: lacks block"),
], ids=["cell-name", "metric-name", "unit-space", "unit-greek",
        "moves-not-reported", "four-chips", "config-file", "traffic-file",
        "metric-file", "reduced-width", "bound", "extra-key",
        "family-unknown", "family-path", "family-incomplete"])
def test_broken_manifest_is_refused(tmp_path, edit, needle):
    problems = _broken(tmp_path, edit)
    assert any(needle in p for p in problems), problems
