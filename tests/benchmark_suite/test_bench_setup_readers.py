"""The seven `setup_*` readers (readers/_setup.py): sums over the program's
own set-up records, one a compiled program, that closed inside set-up."""

import json

import pytest

from benchmark import common, run
from benchmark.manifest import Manifest
from benchmark.readers import _setup
from benchmark.runners import result
from paddle_tpu.core import telemetry

from . import toy

REAL = Manifest(toy.REPO)
NEW = ("setup_programs_s", "setup_build_s", "setup_infer_shape_s",
       "setup_trace_lower_s", "setup_compile_s", "setup_first_run_s",
       "setup_outside_programs_s")
SETUP_S = 50.0


def record(name, t1, **seconds):
    """A set-up record as `telemetry.compile_records()` hands it out, `t1`
    in seconds since the process began measuring; `first_run_s` is what the
    other parts leave of `total_s`, as the program computes it."""
    doc = dict.fromkeys(_setup.SECONDS, 0.0)
    doc.update(seconds)
    doc["first_run_s"] = doc["total_s"] - sum(
        doc[k] for k in ("build_s", "trace_s", "lower_s", "compile_s",
                         "cache_read_s", "capture_s"))
    return dict(doc, name=name, kind="decode", ops=7, cache_hit=True,
                t0=common._T0 + t1 - doc["total_s"], t1=common._T0 + t1)


RECORDS = [
    record("decode_step_b64", 20.0, total_s=8.0, build_s=2.0,
           infer_shape_s=1.5, trace_s=3.0, lower_s=1.0, cache_read_s=1.5),
    record("prefill_p4096", 31.0, total_s=10.0, build_s=1.0,
           infer_shape_s=0.75, trace_s=2.0, lower_s=2.5, compile_s=3.0,
           cache_read_s=0.25, capture_s=0.5),
    # compiled by a request inside the window: no part of set-up
    record("prefill_p8192", 64.0, total_s=9.0, build_s=1.0, trace_s=4.0),
]
WORKED = {
    "setup_programs_s": 18.0,
    "setup_build_s": 3.0,
    "setup_infer_shape_s": 5.25,       # the process's, not the records'
    "setup_trace_lower_s": 8.5,
    "setup_compile_s": 4.75,
    "setup_first_run_s": 1.25,         # 0.5 of the step, 0.75 of the prefill
    "setup_outside_programs_s": 32.0,
}


@pytest.fixture
def records(monkeypatch):
    """The program's record list and shape-inference pair, by hand."""
    held = list(RECORDS)
    monkeypatch.setattr(telemetry, "compile_records", lambda: list(held))
    monkeypatch.setattr(telemetry, "infer_shape_totals",
                        lambda: (5.25, 400))
    _setup._records_of_run.cache_clear()
    yield held
    _setup._records_of_run.cache_clear()


def test_the_manifest_is_sound_with_the_seven_entries():
    assert REAL.problems() == []
    cells = [w["name"] for w in REAL.doc["workloads"]][:9]
    assert [m["name"] for m in REAL.doc["per_layer"][-7:]] == list(NEW)
    for name in NEW:
        entry = toy.entry(REAL, "per_layer", name)
        assert entry == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_span", "layer": "program to step",
            "moves": "setup_s", "workloads": cells}


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_sums_the_records_that_closed_inside_setup(metric, records,
                                                            capsys):
    read = REAL.reader(metric)
    for kind in ("serve", "train"):
        assert read(result(kind=kind, setup_s=SETUP_S)) == pytest.approx(
            WORKED[metric])
    # one progress line a run holds the table the sums are of
    lines = [json.loads(line) for line in capsys.readouterr().out.split("\n")
             if line.startswith('{"phase": "setup.programs"')]
    assert len(lines) == 1
    assert [p["name"] for p in lines[0]["programs"]] == [
        "decode_step_b64", "prefill_p4096"]
    assert lines[0]["programs"][1]["t1"] == 31.0
    # a later window opening takes the third program in
    if metric == "setup_programs_s":
        assert read(result(kind="serve", setup_s=64.5)) == 27.0


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_reads_none_where_there_is_no_record(metric, records,
                                                      monkeypatch):
    read = REAL.reader(metric)
    assert read(result(kind="serve", setup_s=10.0)) is None   # all later
    assert read(result(kind="serve")) is None                 # no window
    del records[:]
    _setup._records_of_run.cache_clear()
    assert read(result(kind="serve", setup_s=SETUP_S)) is None
    # the parent commit's program keeps no such list
    monkeypatch.delattr(telemetry, "compile_records")
    _setup._records_of_run.cache_clear()
    assert read(result(kind="train", setup_s=SETUP_S)) is None


def test_the_parts_tile_what_the_programs_took(records):
    ctx = result(kind="serve", setup_s=SETUP_S)
    value = {m: REAL.reader(m)(ctx) for m in NEW}
    assert value["setup_programs_s"] + value[
        "setup_outside_programs_s"] == SETUP_S
    capture = sum(r["capture_s"] for r in RECORDS[:2])
    assert (value["setup_build_s"] + value["setup_trace_lower_s"]
            + value["setup_compile_s"] + value["setup_first_run_s"]
            ) == pytest.approx(value["setup_programs_s"] - capture)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.keep_cells(
        toy.make_root(str(tmp_path_factory.mktemp("setup_root"))),
        {"toy_train": "setup_train", "toy_closed": "setup_closed",
         "toy_open": "setup_open"})


def test_an_entry_of_both_kinds_maps_to_all_three_toy_cells(root):
    man = Manifest(root)
    assert man.problems() == []
    for cell in ("setup_train", "setup_closed", "setup_open"):
        assert set(NEW) | {"setup_s"} <= toy.reported(man, cell)


@pytest.mark.parametrize("cell, programs", [
    # the start-up program interpreted, then the step: the check's pair at
    # its own depth, then the cell's
    ("setup_train", ["interpreted", "executor", "interpreted", "executor"]),
    # the engine's: a step bucket and two prefill buckets
    ("setup_closed", ["decode", "decode", "decode"]),
])
def test_the_rehearsal_reads_set_up_by_program(root, cell, programs, capsys):
    # the rehearsal runs many cells in one process, the chip one
    telemetry.clear_compile_records()
    _setup._records_of_run.cache_clear()
    out = run.run_cell(root, cell, seed=7, seconds=1.0, trace=True,
                       require_platform=None)
    assert out["correct"] is True
    value = {m: out["metrics"][m]["value"] for m in NEW}
    assert value["setup_programs_s"] > 0
    assert value["setup_outside_programs_s"] >= 0
    assert (value["setup_build_s"] + value["setup_trace_lower_s"]
            + value["setup_compile_s"] + value["setup_first_run_s"]
            ) == pytest.approx(value["setup_programs_s"], rel=0.02)
    assert value["setup_infer_shape_s"] > 0
    line, = [json.loads(text)
             for text in capsys.readouterr().out.split("\n")
             if text.startswith('{"phase": "setup.programs"')]
    assert [p["kind"] for p in line["programs"]] == programs
    # an end-to-end run reports none of them
    out = run.run_cell(root, cell, seed=8, seconds=0.5, trace=False,
                       require_platform=None)
    assert not set(NEW) & set(out["metrics"])
