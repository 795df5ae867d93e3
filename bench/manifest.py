"""BENCHMARK.json and the files it names, found by name.

A cell (an entry of ``workloads``) names a configuration, whose ``file``
is given in the manifest, and a traffic mix, read from
``bench/traffic/<traffic>.json``; the mix's ``kind`` names its generator,
``bench/traffic/gen_<kind>.py``.  Each metric is read by
``bench/metrics/<name>.py``, or, for a variant such as
``device_idle_pct.chat``, by the reader of the name before its first dot.
A configuration file's ``reference`` key names its model family,
``bench/families/<reference>.py`` (the interface is in
``bench/families/README.md``): the program's model configuration and
parameter layout, the float32 reference layer, and the operation counts
of that architecture.  So a later change adds a configuration, a model
family, a mix or a metric by adding files and entries only.

``problems()`` is the manifest check the tests run.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Dict, List, Optional

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
# keys that name a width, which no cut may change
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|_proj|"
                   r"head_dim|_dim$|_rank$|expansion|expand|"
                   r"experts_per_tok)")


# what a family module has to define (``head`` and ``KERNELS`` are
# optional: ``reference.default_head`` and ``()``)
FAMILY_NAMES = ("program", "params", "layer_shapes", "MATRICES", "block",
                "dims", "layer_matmul_params", "param_count",
                "kv_bytes_per_token")
_FAMILIES: Dict[str, object] = {}    # file -> module: each runs once


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_of(cfg: dict):
    """The model family of a configuration: the module in the file its
    ``family_file`` names (``Manifest.config`` sets it to the checkout's
    ``bench/families/<reference>.py``)."""
    path = cfg["family_file"]
    if path not in _FAMILIES:
        _FAMILIES[path] = _load_module(
            path, "family_" + os.path.basename(path)[:-3])
    return _FAMILIES[path]


class Manifest:
    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.data = json.load(f)
        self.bench = os.path.join(root, "bench")

    # -- lookup -----------------------------------------------------------
    def cell(self, name: str) -> dict:
        for c in self.data["workloads"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        """The configuration file, with ``family_file``: this checkout's
        ``bench/families/<reference>.py``."""
        with open(os.path.join(self.root,
                               self.config_entry(cell["config"])["file"])) as f:
            cfg = json.load(f)
        cfg["family_file"] = self.family_path(cfg.get("reference"))
        return cfg

    def family_path(self, reference) -> str:
        return os.path.join(self.bench, "families", f"{reference}.py")

    def traffic_path(self, name: str) -> str:
        return os.path.join(self.bench, "traffic", f"{name}.json")

    def traffic(self, cell: dict) -> dict:
        with open(self.traffic_path(cell["traffic"])) as f:
            return json.load(f)

    def generator(self, kind: str):
        return _load_module(os.path.join(self.bench, "traffic",
                                         f"gen_{kind}.py"), f"gen_{kind}")

    def metric_path(self, name: str) -> Optional[str]:
        d = os.path.join(self.bench, "metrics")
        for n in (name, name.split(".", 1)[0]):
            p = os.path.join(d, f"{n}.py")
            if os.path.exists(p):
                return p
        return None

    def reader(self, name: str):
        path = self.metric_path(name)
        if path is None:
            raise KeyError(f"no reader bench/metrics/<{name}>.py")
        return _load_module(path, "metric_" + name.replace(".", "_")
                            .replace("-", "_")).read

    def peaks(self, device_kind: str) -> Optional[dict]:
        with open(os.path.join(self.bench, "peaks.json")) as f:
            return json.load(f)["devices"].get(device_kind)

    def metrics_for(self, cell: dict, kind: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell
        reports."""
        return [m for m in self.data[kind]
                if "workloads" not in m or cell["name"] in m["workloads"]]

    # -- the check ------------------------------------------------------
    def problems(self) -> List[str]:
        d, out = self.data, []
        if set(d) != KEYS:
            out.append(f"top-level keys {sorted(d)} != {sorted(KEYS)}")
        cells = {c["name"]: c for c in d.get("workloads", [])}
        configs = {c["name"]: c for c in d.get("configs", [])}
        e2e = {m["name"]: m for m in d.get("end_to_end", [])}
        for p in d.get("paths", []):
            if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
                out.append(f"bad path {p!r}")
        for word in d.get("command", []):
            if word.startswith("/") or ".." in word.split("/"):
                out.append(f"command word {word!r} leaves the checkout")
        for kind, keys in (("configs", CONFIG_KEYS), ("workloads", CELL_KEYS),
                           ("end_to_end", E2E_KEYS),
                           ("per_layer", LAYER_KEYS)):
            names = [e.get("name") for e in d.get(kind, [])]
            if len(set(names)) != len(names):
                out.append(f"{kind}: duplicate names")
            for e in d.get(kind, []):
                extra = set(e) - keys - ({"workloads"} if kind in (
                    "end_to_end", "per_layer") else set())
                if extra or keys - set(e):
                    out.append(f"{kind} {e.get('name')}: keys {sorted(e)}")
                for k in ("name", "config", "traffic"):
                    if k in e and not NAME.match(str(e[k])):
                        out.append(f"{kind}: bad {k} {e[k]!r}")
                if "unit" in e and not UNIT.match(e["unit"]):
                    out.append(f"{kind} {e['name']}: bad unit {e['unit']!r}")
                if "better" in e and e["better"] not in ("lower", "higher"):
                    out.append(f"{kind} {e['name']}: better {e['better']!r}")
                for k in ("why", "layer", "source"):
                    v = e.get(k)
                    if isinstance(v, str) and not (
                            0 < len(v) <= 200 and "\n" not in v
                            and "\t" not in v):
                        out.append(f"{kind} {e.get('name')}: bad {k}")
                for w in e.get("workloads", []):
                    if w not in cells:
                        out.append(f"{kind} {e['name']}: unknown cell {w}")
        for c in configs.values():
            path = os.path.join(self.root, c["file"])
            if not os.path.exists(path):
                out.append(f"config {c['name']}: missing {c['file']}")
            else:
                out += self._family_problems(c["name"], path)
            for k in c["reduced"]:
                if not NAME.match(k) or WIDTH.search(k):
                    out.append(f"config {c['name']}: reduced {k!r}")
            if not any(w["config"] == c["name"] for w in cells.values()):
                out.append(f"config {c['name']}: used by no cell")
        four = sum(1 for c in cells.values() if c["chips"] == 4)
        if four > max(1, len(cells) // 2):
            out.append(f"{four} of {len(cells)} cells ask for 4 chips")
        for c in cells.values():
            if c["chips"] not in (1, 4):
                out.append(f"cell {c['name']}: chips {c['chips']}")
            if c["config"] not in configs:
                out.append(f"cell {c['name']}: unknown config")
            if not os.path.exists(self.traffic_path(c["traffic"])):
                out.append(f"cell {c['name']}: missing traffic file")
            else:
                kind = self.traffic(c)["kind"]
                if not os.path.exists(os.path.join(
                        self.bench, "traffic", f"gen_{kind}.py")):
                    out.append(f"cell {c['name']}: no generator {kind}")
            reported = [m["name"] for m in self.metrics_for(c, "end_to_end")]
            if "setup_s" not in reported or len(reported) < 2:
                out.append(f"cell {c['name']}: end-to-end {reported}")
            if not self.metrics_for(c, "per_layer"):
                out.append(f"cell {c['name']}: no per-layer metric")
        for m in d.get("per_layer", []):
            if m["moves"] not in e2e:
                out.append(f"{m['name']}: moves unknown {m['moves']!r}")
                continue
            for w in m.get("workloads", list(cells)):
                if w in cells and m["moves"] not in [
                        x["name"] for x in self.metrics_for(cells[w],
                                                            "end_to_end")]:
                    out.append(f"{m['name']}: cell {w} does not report "
                               f"{m['moves']}")
        for m in list(d.get("end_to_end", [])) + list(d.get("per_layer", [])):
            if self.metric_path(m["name"]) is None:
                out.append(f"{m['name']}: no reader file")
        for m in d.get("end_to_end", []):
            lim = 0.25
            if not 0.01 <= m.get("bound", 0) <= lim:
                out.append(f"{m['name']}: bound {m.get('bound')}")
            if m["source"] not in ("host_clock", "device_trace"):
                out.append(f"{m['name']}: source {m['source']}")
        rs = d.get("run_seconds")
        if not (isinstance(rs, int) and 1 <= rs <= 51):
            out.append(f"run_seconds {rs}")
        if len(json.dumps(d)) > 64 * 1024:
            out.append("BENCHMARK.json over 64 KiB")
        return out

    def _family_problems(self, config: str, path: str) -> List[str]:
        with open(path) as f:
            ref = json.load(f).get("reference")
        if not (isinstance(ref, str) and NAME.match(ref)
                and os.path.exists(self.family_path(ref))):
            return [f"config {config}: reference {ref!r} names no file "
                    "under bench/families/"]
        family = family_of({"family_file": self.family_path(ref)})
        missing = [n for n in FAMILY_NAMES if not hasattr(family, n)]
        return [f"family {ref}: lacks {', '.join(missing)}"] if missing \
            else []
