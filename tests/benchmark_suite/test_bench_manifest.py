"""BENCHMARK.json and the files it names: the rules the harness depends on,
and that a cell, a configuration and a metric are added by files alone
(a served model of another family: test_bench_families.py)."""

import json
import os
import re

import pytest

from benchmark.manifest import FAMILY_FUNCTIONS, RUNNERS_DIR, Manifest

from . import toy


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
