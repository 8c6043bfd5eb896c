"""trace_reduce.py: the interval arithmetic on made-up events, and the whole
reduction on a small trace recorded once on the CPU (data/)."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
XPLANE = os.path.join(HERE, "data", "cpu_small.xplane.pb")


def test_union_merges_overlaps_and_drops_empty_intervals():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)]) \
        == [(0, 4), (5, 12)]


def test_gaps_are_the_complement_inside_the_window():
    assert tr.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([(0, 10)], 0, 10) == []


def test_every_idle_instant_goes_to_the_innermost_span_open_then():
    """One gap that runs across three spans is split among them; the window
    span names nothing, and what no span covers takes the default label."""
    spans = [("bench.window", 0, 100), ("decode.loop_ms", 10, 80),
             ("decode.step_ms", 20, 60), ("decode.fetch_ms", 40, 60)]
    device = {"/device:TPU:0": [("fusion.1", 20, 50)]}
    r = tr.reduce_events(device, spans, default_gap_label="host")
    assert dict(r["idle_gaps"]) == pytest.approx({
        "host": 30e-9,                  # 0-10 and 80-100
        "decode.loop_ms": 30e-9,        # 10-20 and 60-80
        "decode.fetch_ms": 10e-9})      # 50-60; none under step_ms itself
    assert r["idle_gaps"][0][0] == "decode.loop_ms"     # ties: by name
    assert sum(v for _, v in r["idle_gaps"]) + r["busy_s"] \
        == pytest.approx(r["window_s"])


def test_the_trace_is_read_for_the_programs_spans_too():
    assert tr.SPAN_PREFIXES == ("bench.", "decode.", "executor.")
    assert "decode.sample_ms".startswith(tr.SPAN_PREFIXES)
    assert not "jit_train_step".startswith(tr.SPAN_PREFIXES)


def test_op_names_fold_into_families():
    assert tr.op_family("%copy-done.195 = f32[2048]{0} copy-done(%x)") \
        == "copy-done"
    assert tr.op_family("%convolution_tanh_fusion = bf16[8]") \
        == "convolution_tanh_fusion"
    assert tr.op_family("dot_general") == "dot_general"


def test_reduce_events_on_two_devices_by_hand():
    device = {"/device:TPU:0": [("a.1", 0, 40), ("all-reduce.2", 40, 60),
                                ("a.3", 50, 70)],
              "/device:TPU:1": [("a.1", 10, 30), ("b", 80, 120)]}
    modules = {"/device:TPU:0": [("jit_step(1)", 0, 70), ("jit_step(1)", 90, 110)],
               "/device:TPU:1": [("jit_step(1)", 10, 30)]}
    spans = [("bench.window", 0, 100), ("bench.feed", 70, 100)]
    r = tr.reduce_events(device, spans, modules, "host")
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s_per_device"] == pytest.approx([70e-9, 40e-9])
    assert r["busy_s"] == pytest.approx(55e-9)
    assert r["device_ops"][0] == ["a", pytest.approx(40e-9)]
    # every family, not the ten largest: a reader asks for one by name
    assert r["op_seconds"] == pytest.approx({
        "a": 40e-9, "b": 10e-9, "all-reduce": 10e-9})
    assert [k for k, _ in r["device_ops"]] == list(r["op_seconds"])
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # device 0 idles 70-100 under bench.feed; device 1 0-10 and 30-70 under
    # no span and 70-80 under bench.feed
    assert gaps["bench.feed"] == pytest.approx(20e-9)
    assert gaps["host"] == pytest.approx(25e-9)
    step = r["programs"]["jit_step(1)"]
    assert step["runs"] == pytest.approx((1 + 0.5 + 1) / 2)
    assert step["seconds"] == pytest.approx((70 + 10 + 20) / 2 * 1e-9)


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce_events({}, [])


@pytest.fixture(scope="module")
def recorded():
    return tr.read_events(
        XPLANE, is_device_plane=lambda n: n == "/host:CPU",
        is_ops_line=lambda n: n.startswith("tf_XLA"))


def test_the_recorded_trace_reduces_to_its_pinned_numbers(recorded):
    assert os.path.getsize(XPLANE) < 1 << 20
    device, modules, spans = recorded
    assert sorted({n for n, _, _ in spans}) == [
        "bench.block", "bench.exe_run", "bench.feed", "bench.window"]
    r = tr.reduce_events(device, spans, modules, "host")
    assert r["window_s"] == pytest.approx(0.015416828, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.004488471, abs=1e-9)
    assert r["device_ops"][0] == ["dot_general",
                                  pytest.approx(0.004091849, abs=1e-9)]
    assert r["op_seconds"]["wrapped_tanh"] == pytest.approx(0.000198361,
                                                            abs=1e-9)
    assert len(r["op_seconds"]) == 9 and len(r["device_ops"]) == 9
    assert [[k, pytest.approx(v, abs=1e-9)] for k, v in [
        ("bench.feed", 0.009731805), ("bench.exe_run", 0.000915351),
        ("bench.block", 0.000194092), ("host", 0.000087109)]] \
        == r["idle_gaps"]


def test_the_recorded_union_agrees_with_a_sweep_over_endpoints(recorded):
    """The same busy time by another method: count open intervals while
    walking the sorted endpoints."""
    device, _, spans = recorded
    lo, hi = next((s, e) for n, s, e in spans if n == tr.WINDOW_SPAN)
    points = []
    for evs in device.values():
        for _, s, e in evs:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                points += [(s, 1), (e, -1)]
    busy = open_now = 0
    last = None
    for t, step in sorted(points):
        if open_now > 0:
            busy += t - last
        open_now += step
        last = t
    r = tr.reduce_events(device, spans)
    assert r["busy_s"] == pytest.approx(busy / 1e9, abs=1e-12)
    idle = sum(v for _, v in r["idle_gaps"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"], abs=1e-9)
