"""`step_ahead_share.serve`: the share of a window's decode steps that the
engine dispatched before it had fetched the step before them."""

import pytest

from benchmark import run
from benchmark.manifest import Manifest
from benchmark.runners import result

from . import toy

METRIC = "step_ahead_share.serve"


def test_the_manifest_is_sound_with_the_entry_at_its_end():
    real = Manifest(toy.REPO)
    assert real.problems() == []
    entry = real.doc["per_layer"][-1]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "decode engine",
        "moves": "serve_tokens_per_s",
        "workloads": ["xglm_1p7b_serve_closed_c16",
                      "trinity_large_tp8ep8_serve_closed_c96"]}
    for cell in entry["workloads"]:
        assert real.config_doc(real.cell(cell)["config"])["kind"] == "serve"
        assert "serve_tokens_per_s" in {
            m["name"] for m in real.metrics_of(cell, "end_to_end")}


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
