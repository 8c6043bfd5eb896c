"""BENCHMARK.json and the files it names: loading and the checks on them.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found by the name in BENCHMARK.json:

  configuration  the `file` its entry gives (benchmark/configs/<name>.json)
  traffic mix    <data>/traffic/<traffic>.json, read by the generator it names
  metric         <data>/readers/<metric>.py with a `read(ctx)`
  family         <data>/families/<family>.py with FAMILY_FUNCTIONS: what a
                 serving configuration's `family` key names

`<data>` is the first directory of `paths`. A configuration's `kind` picks
the runner, `runners/<kind>.py` beside this file. A later PR adds files and
entries and edits nothing here.
"""

from __future__ import annotations

import ast
import importlib.util
import json
import os
import re
from typing import Dict, List

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
RUNNERS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "runners")
FAMILY_FUNCTIONS = ("model_config", "make_params", "make_engine", "slots",
                    "traffic_vocab", "check_correct", "step_bytes")


class ManifestError(ValueError):
    pass


class Manifest:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self.data_dir = os.path.join(self.root, self.doc["paths"][0])
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    # -- lookups -------------------------------------------------------------
    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise ManifestError(f"no workload {name!r} in BENCHMARK.json; "
                                f"it has {sorted(self.cells)}")
        return self.cells[name]

    def config_doc(self, name: str) -> dict:
        with open(os.path.join(self.root, self.configs[name]["file"])) as f:
            return json.load(f)

    def traffic_path(self, traffic: str) -> str:
        return os.path.join(self.data_dir, "traffic", traffic + ".json")

    def traffic_doc(self, traffic: str) -> dict:
        with open(self.traffic_path(traffic)) as f:
            return json.load(f)

    def reader_path(self, metric: str) -> str:
        return os.path.join(self.data_dir, "readers", metric + ".py")

    def reader(self, metric: str):
        return _load("reader", metric, self.reader_path(metric)).read

    def family_path(self, family: str) -> str:
        return os.path.join(self.data_dir, "families", family + ".py")

    def family(self, family: str):
        """The module of a served model's family (FAMILY_FUNCTIONS)."""
        return _load("family", family, self.family_path(family))

    def metrics_of(self, cell: str, group: str) -> List[dict]:
        """The `end_to_end` or `per_layer` metrics this cell reports."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell in m["workloads"]]

    # -- checks --------------------------------------------------------------
    def problems(self) -> List[str]:
        """Every way this manifest and its files break the rules the
        harness depends on; empty when sound."""
        d, out = self.doc, []
        if set(d) != KEYS:
            out.append(f"keys {sorted(set(d) ^ KEYS)} missing or unknown")
            return out
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [e["name"] for e in d[group]]
            out += [f"{group}: bad name {n!r}" for n in names
                    if not NAME_RE.match(n)]
            if len(set(names)) != len(names):
                out.append(f"{group}: a name appears twice")
        metric_names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
        if len(set(metric_names)) != len(metric_names):
            out.append("two metrics share a name")
        e2e = {m["name"]: m for m in d["end_to_end"]}
        if "setup_s" not in e2e:
            out.append("no setup_s among end_to_end")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT_RE.match(m["unit"]):
                out.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"{m['name']}: better is {m['better']!r}")
            if m["source"] not in SOURCES:
                out.append(f"{m['name']}: source {m['source']!r}")
            for w in m.get("workloads", ()):
                if w not in self.cells:
                    out.append(f"{m['name']}: unknown workload {w!r}")
            if not os.path.isfile(self.reader_path(m["name"])):
                out.append(f"{m['name']}: no reader file")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                out.append(f"{m['name']}: end-to-end source {m['source']!r}")
            if not 0 < m["bound"] <= 0.1:
                out.append(f"{m['name']}: bound {m['bound']}")
        for m in d["per_layer"]:
            if m["moves"] not in e2e:
                out.append(f"{m['name']}: moves unknown {m['moves']!r}")
                continue
            moved = e2e[m["moves"]]
            for cell in m.get("workloads", self.cells):
                if "workloads" in moved and cell not in moved["workloads"]:
                    out.append(f"{m['name']} is reported in {cell} but "
                               f"{m['moves']}, which it moves, is not")
        used = set()
        pairs = set()
        for w in d["workloads"]:
            used.add(w["config"])
            if w["config"] not in self.configs:
                out.append(f"{w['name']}: unknown config {w['config']!r}")
            if not NAME_RE.match(w["traffic"]):
                out.append(f"{w['name']}: bad traffic name")
            if not os.path.isfile(self.traffic_path(w["traffic"])):
                out.append(f"{w['name']}: no traffic file {w['traffic']}.json")
            if w["chips"] not in (1, 4):
                out.append(f"{w['name']}: chips {w['chips']}")
            if (w["config"], w["traffic"]) in pairs:
                out.append(f"{w['name']}: pair of config and traffic twice")
            pairs.add((w["config"], w["traffic"]))
            if len(w["why"]) > 200:
                out.append(f"{w['name']}: why over 200 characters")
            names = {g: [m["name"] for m in self.metrics_of(w["name"], g)]
                     for g in ("end_to_end", "per_layer")}
            if len(names["end_to_end"]) < 2 or not names["per_layer"]:
                out.append(f"{w['name']}: needs setup_s, another end-to-end "
                           f"metric and a per-layer metric")
        for c in d["configs"]:
            if c["name"] not in used:
                out.append(f"config {c['name']} is used by no cell")
            if not os.path.isfile(os.path.join(self.root, c["file"])):
                out.append(f"config {c['name']}: no file {c['file']}")
            if not any(c["file"].startswith(p.rstrip("/") + "/")
                       for p in d["paths"]):
                out.append(f"config {c['name']}: file outside paths")
            out += [f"config {c['name']}: bad reduced key {k!r}"
                    for k in c["reduced"] if not NAME_RE.match(k)]
            if os.path.isfile(os.path.join(self.root, c["file"])):
                out += self._runner_problems(c["name"])
        four = sum(1 for w in d["workloads"] if w["chips"] == 4)
        if four > max(1, len(d["workloads"]) // 4):
            out.append(f"{four} four-chip cells of {len(d['workloads'])}")
        return out

    def _runner_problems(self, config: str) -> List[str]:
        """A configuration's `kind` needs its runner file and, served, its
        family file with every function the runner calls: found here, not
        when a chip run dies."""
        doc = self.config_doc(config)
        kind = str(doc.get("kind"))
        if not (NAME_RE.match(kind) and os.path.isfile(
                os.path.join(RUNNERS_DIR, kind + ".py"))):
            return [f"config {config}: no runner file runners/{kind}.py "
                    f"for kind {kind!r}"]
        if kind != "serve":
            return []
        family = doc.get("family")
        if not (isinstance(family, str) and NAME_RE.match(family)):
            return [f"config {config}: a served configuration names its "
                    f"`family`, this one has {family!r}"]
        path = self.family_path(family)
        if not os.path.isfile(path):
            return [f"config {config}: no family file "
                    f"families/{family}.py"]
        with open(path) as f:
            defined = {node.name for node in ast.parse(f.read()).body
                       if isinstance(node, ast.FunctionDef)}
        return [f"config {config}: family {family} lacks {fn}()"
                for fn in FAMILY_FUNCTIONS if fn not in defined]


def _load(what: str, name: str, path: str):
    """A reader's or a family's module, by its file: the file may lie in
    any root, so it is no member of a package."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{what}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
