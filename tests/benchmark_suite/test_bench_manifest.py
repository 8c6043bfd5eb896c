"""BENCHMARK.json and the files it names: the rules the harness depends on,
that a cell, a configuration and a metric are added by files alone (a
served model of another family: test_bench_families.py), and that no
family's test stops the next cell: each holds beside a further one."""

import copy
import glob
import importlib
import json
import os
import re

import pytest

from benchmark.manifest import FAMILY_FUNCTIONS, RUNNERS_DIR, Manifest

from . import toy

# A family's test file says what is true of ITS cell and ITS metrics in a
# `holds(man)` (benchmark/README.md). Every file of this directory that
# has one is a case below: a new family's file joins by being there.
FAMILY_TESTS = {}
for _path in sorted(glob.glob(os.path.join(os.path.dirname(__file__),
                                           "test_bench_*.py"))):
    _stem = os.path.splitext(os.path.basename(_path))[0]
    if _stem != __name__.rpartition(".")[2]:
        _module = importlib.import_module("." + _stem, __package__)
        if hasattr(_module, "holds"):
            FAMILY_TESTS[_stem[len("test_bench_"):]] = _module


@pytest.fixture(scope="module")
def real():
    return Manifest(toy.REPO)


def test_the_repo_manifest_is_sound(real):
    assert real.problems() == []


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_and_units_use_only_the_allowed_characters(real, group):
    for entry in real.doc[group]:
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}",
                            entry["name"]), entry["name"]
        if "unit" in entry:
            assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry["unit"])
        for text in (entry.get("why"), entry.get("layer"),
                     entry.get("source")):
            assert text is None or (0 < len(text) <= 200
                                    and "\n" not in text and "\t" not in text)


def test_every_layer_metric_moves_a_metric_its_cells_report(real):
    for cell in real.cells:
        reported = {m["name"] for m in real.metrics_of(cell, "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layer = real.metrics_of(cell, "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in reported, (cell, m["name"])


def test_every_named_file_exists(real):
    for c in real.doc["configs"]:
        doc = real.config_doc(c["name"])
        # README: a configuration of a new kind brings runners/<kind>.py,
        # a served model of another family brings families/<family>.py
        assert os.path.isfile(os.path.join(RUNNERS_DIR, doc["kind"] + ".py"))
        if doc["kind"] == "serve":
            family = real.family(doc["family"])
            for fn in FAMILY_FUNCTIONS:
                assert callable(getattr(family, fn)), (c["name"], fn)
        assert doc["reduced"] == c["reduced"]
        for key in ("source", "assumed", "departures"):
            assert doc[key], (c["name"], key)
    for w in real.doc["workloads"]:
        assert real.traffic_doc(w["traffic"])["generator"]
    for m in real.doc["end_to_end"] + real.doc["per_layer"]:
        assert callable(real.reader(m["name"]))


def test_at_most_one_cell_in_four_asks_for_four_chips(real):
    four = [w for w in real.doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(real.doc["workloads"]) // 4)


class Grown(Manifest):
    """The repo's manifest as the next served cell's PR leaves it, in
    memory: a further configuration, a further cell at the end of
    `workloads`, three per-layer entries of its own at the end of theirs,
    and its name at the end of the lists of what every served loop at
    saturation reports. The files are a served cell's that is there."""

    CELL = "further_family_serve_closed"
    OWN = ("further_kernel_roofline", "further_step_roofline",
           "further_busy_share.serve")
    JOINED = ("serve_tokens_per_s", "batch_occupancy_avg",
              "completed_requests_per_s", "window_hbm_gb.serve",
              "prefill_time_share.serve", "step_ahead_share.serve")

    def __init__(self, root):
        super().__init__(root)
        doc = copy.deepcopy(self.doc)
        like = next(w for w in doc["workloads"] if self.config_doc(
            w["config"])["kind"] == "serve")
        doc["configs"].append(dict(self.configs[like["config"]],
                                   name="further_family"))
        doc["workloads"].append(dict(like, name=self.CELL,
                                     config="further_family"))
        for m in doc["end_to_end"] + doc["per_layer"]:
            if m["name"] in self.JOINED:
                m["workloads"].append(self.CELL)
        doc["per_layer"] += [
            {"name": name, "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "kernels and step program",
             "moves": "serve_tokens_per_s", "workloads": [self.CELL]}
            for name in self.OWN]
        self.doc = doc
        self.cells = {w["name"]: w for w in doc["workloads"]}
        self.configs = {c["name"]: c for c in doc["configs"]}

    def reader_path(self, metric):
        """The three have no file: a reader that is there stands in."""
        return super().reader_path(
            "batch_occupancy_avg" if metric in self.OWN else metric)


@pytest.fixture(scope="module")
def grown():
    grown = Grown(toy.REPO)
    assert grown.problems() == []
    assert toy.reported(grown, Grown.CELL) \
        >= set(Grown.OWN) | set(Grown.JOINED) | {"setup_s"}
    return grown


def test_every_family_with_a_cell_has_its_assertions_in_a_holds(real):
    """The files PR 42 freed, and one of the same name for every family
    whose file came with a cell of its own since."""
    assert {"step_ahead", "afmoe", "kimi_k2", "falcon_h1",
            "admission_readers"} <= set(FAMILY_TESTS)
    for c in real.doc["configs"]:
        family = real.config_doc(c["name"]).get("family", "decoder_lm")
        assert family == "decoder_lm" or family in FAMILY_TESTS, family


@pytest.mark.parametrize("family", sorted(FAMILY_TESTS))
def test_a_familys_assertions_hold_beside_a_further_cell(grown, family):
    """A test that pins a list's end or length, a cell's reported metrics
    or a metric's cells with `==`, fails here on the day it is written and
    not on the day of the next cell."""
    FAMILY_TESTS[family].holds(grown)


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    root = toy.make_root(str(tmp_path), extra_metric={
        "name": "dummy_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "trainer step",
        "moves": "train_tokens_per_s", "workloads": ["toy_train"]})
    reader = os.path.join(root, "benchmark", "readers", "dummy_steps.py")
    assert "no reader file" in " ".join(Manifest(root).problems())
    with open(reader, "w") as f:
        f.write("def read(ctx):\n    return ctx.steps\n")
    man = Manifest(root)
    assert man.problems() == []
    assert "dummy_steps" in [m["name"] for m in
                             man.metrics_of("toy_train", "per_layer")]
    assert "dummy_steps" not in [m["name"] for m in
                                 man.metrics_of("toy_open", "per_layer")]


@pytest.mark.parametrize("breakage, said", [
    (lambda d: d["per_layer"][0].update(moves="train_tokens_per_s"),
     "which it moves, is not"),
    (lambda d: d["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda d: d["workloads"][0].update(traffic="nowhere"),
     "no traffic file"),
    (lambda d: d["workloads"].append(dict(d["workloads"][0], name="twin")),
     "pair of config and traffic twice"),
    (lambda d: d["end_to_end"][0].update(unit="tokens per second"),
     "bad unit"),
])
def test_a_broken_manifest_is_named(tmp_path, breakage, said):
    root = toy.make_root(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    breakage(doc)
    with open(path, "w") as f:
        json.dump(doc, f)
    assert said in " | ".join(Manifest(root).problems())


def _rewrite_config(root, name, change):
    path = os.path.join(root, "benchmark", "configs", name + ".json")
    with open(path) as f:
        doc = json.load(f)
    doc.update(change)
    with open(path, "w") as f:
        json.dump(doc, f)


@pytest.mark.parametrize("config, change, family_source, said", [
    ("toy_bert", {"kind": "rank"}, None,
     "config toy_bert: no runner file runners/rank.py"),
    ("toy_lm", {"family": "nowhere"}, None,
     "config toy_lm: no family file families/nowhere.py"),
    ("toy_lm", {"family": "half"},
     "def model_config(config):\n    pass\n\n\nstep_bytes = 7\n",
     "family half lacks step_bytes()"),
])
def test_a_configuration_without_its_runner_or_family_is_named(
        tmp_path, config, change, family_source, said):
    """What a chip run would die of is a problem of the manifest."""
    root = toy.make_root(str(tmp_path))
    assert Manifest(root).problems() == []
    _rewrite_config(root, config, change)
    if family_source:
        with open(os.path.join(root, "benchmark", "families",
                               change["family"] + ".py"), "w") as f:
            f.write(family_source)
    problems = Manifest(root).problems()
    assert said in " | ".join(problems)
    if family_source:      # every missing function is named, none that is there
        lacking = {p.split(" lacks ")[1] for p in problems if " lacks " in p}
        assert lacking == {fn + "()" for fn in FAMILY_FUNCTIONS} \
            - {"model_config()"}


def test_a_served_configuration_names_its_family(tmp_path):
    """No default: the file says what it runs."""
    root = toy.make_root(str(tmp_path))
    path = os.path.join(root, "benchmark", "configs", "toy_lm.json")
    with open(path) as f:
        doc = json.load(f)
    del doc["family"]
    with open(path, "w") as f:
        json.dump(doc, f)
    assert ("config toy_lm: a served configuration names its `family`, "
            "this one has None") in Manifest(root).problems()
