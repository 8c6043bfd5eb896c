"""`step_ahead_share.serve`: the share of a window's decode steps that the
engine dispatched before it had fetched the step before them."""

import pytest

from benchmark import run
from benchmark.manifest import Manifest
from benchmark.runners import result

from . import toy

METRIC = "step_ahead_share.serve"


def holds(man):
    """The entry as PR 31 wrote it, wherever it stands in its list; its
    cells at least the two it came with, each a served one that reports
    the end-to-end metric it moves."""
    entry = toy.entry(man, "per_layer", METRIC)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "decode engine",
        "moves": "serve_tokens_per_s"}
    assert {"xglm_1p7b_serve_closed_c16",
            "trinity_large_tp8ep8_serve_closed_c96"} <= set(
        entry["workloads"])
    for cell in entry["workloads"]:
        assert man.config_doc(man.cell(cell)["config"])["kind"] == "serve"
        assert "serve_tokens_per_s" in {
            m["name"] for m in man.metrics_of(cell, "end_to_end")}


def test_the_manifest_lists_the_entry_by_name_over_serving_cells():
    real = Manifest(toy.REPO)
    assert real.problems() == []
    holds(real)


def test_the_reader_divides_the_two_counters():
    read = Manifest(toy.REPO).reader(METRIC)
    counters = {"decode.steps": 400, "decode.steps_ahead": 380}
    assert read(result(kind="serve", telemetry={"counters": counters})) \
        == pytest.approx(95.0)
    # every step dispatched into an empty pipe still reads, as 0
    assert read(result(kind="serve", telemetry={
        "counters": {"decode.steps": 7, "decode.steps_ahead": 0}})) == 0.0
    # the parent commit's loop has no such counter; a window without a step
    # no share; a training run no telemetry
    assert read(result(kind="serve", telemetry={
        "counters": {"decode.steps": 400}})) is None
    assert read(result(kind="serve", telemetry={
        "counters": {"decode.steps": 0, "decode.steps_ahead": 0}})) is None
    assert read(result(kind="serve", telemetry={"counters": {}})) is None
    assert read(result(kind="train")) is None


def test_a_traced_serving_run_reports_the_share(tmp_path):
    root = toy.keep_cells(toy.make_root(str(tmp_path)),
                          {"toy_closed": "ahead_closed"})
    out = run.run_cell(root, "ahead_closed", seed=2 ** 31 + 31, seconds=1.5,
                       trace=True, require_platform=None)
    assert out["correct"] is True and out["failed"] == 0
    got = out["metrics"][METRIC]
    assert got["unit"] == "%" and 0 < got["value"] <= 100
    assert 0 < out["metrics"]["batch_occupancy_avg"]["value"] <= 100
