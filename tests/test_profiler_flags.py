"""Profiler / flags / monitor tests (reference: test_profiler.py,
test_global_var_getter_setter.py, monitor.h stats)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler


def _small_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], stop_gradient=True)
        y = layers.fc(x, 8, act="relu")
        loss = layers.mean(y)
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    return main, startup, loss


class TestProfiler:
    def test_records_ops_and_steps(self, scope, tmp_path):
        main, startup, loss = _small_program()
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        x = np.ones((2, 4), np.float32)
        trace_path = str(tmp_path / "trace.json")
        with profiler.profiler(profile_path=trace_path):
            exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope,
                    use_compiled=False)         # per-op spans
            exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope)
        summary = profiler.summarize()
        assert any(n in summary for n in ("mul", "matmul_v2", "fc"))
        assert "executor::run" in summary
        with open(trace_path) as f:
            trace = json.load(f)
        assert len(trace["traceEvents"]) == len(profiler.events())
        assert all("dur" in e for e in trace["traceEvents"])

    def test_disabled_records_nothing(self, scope):
        profiler.reset_profiler()
        main, startup, loss = _small_program()
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[loss], scope=scope)
        assert profiler.events() == []


class TestFlags:
    def test_get_set_roundtrip(self):
        assert pt.get_flags("FLAGS_check_nan_inf") == \
            {"FLAGS_check_nan_inf": False}
        pt.set_flags({"FLAGS_check_nan_inf": True})
        try:
            assert pt.get_flags("check_nan_inf")["check_nan_inf"] is True
        finally:
            pt.set_flags({"FLAGS_check_nan_inf": False})

    def test_unknown_flag_raises(self):
        with pytest.raises(ValueError, match="unknown flag"):
            pt.get_flags("FLAGS_no_such_flag")

    def test_check_nan_inf_catches(self, scope):
        from paddle_tpu.core.executor import ExecutionError

        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [2], stop_gradient=True)
            y = layers.log(x)       # log(-1) -> NaN
        exe = pt.Executor()
        exe.run(startup, scope=scope, use_compiled=False)
        pt.set_flags({"FLAGS_check_nan_inf": True})
        try:
            with pytest.raises(ExecutionError, match="NaN/Inf"):
                exe.run(main, feed={"x": -np.ones((1, 2), np.float32)},
                        fetch_list=[y], scope=scope)
        finally:
            pt.set_flags({"FLAGS_check_nan_inf": False})


class TestExclusiveTimes:
    """profiler._exclusive_times nesting math (was only exercised
    implicitly through device_profile)."""

    @staticmethod
    def _ev(ts, dur, pid=1, tid=1, name="e"):
        return {"ts": ts, "dur": dur, "pid": pid, "tid": tid, "name": name}

    def test_proper_containment_chain(self):
        from paddle_tpu.profiler import _exclusive_times

        parent = self._ev(0, 100, name="parent")
        child = self._ev(10, 20, name="child")
        grand = self._ev(12, 5, name="grand")
        excl = _exclusive_times([parent, child, grand])
        assert excl[id(parent)] == 80      # 100 - child's 20
        assert excl[id(child)] == 15       # 20 - grand's 5
        assert id(grand) not in excl       # leaf: inclusive == exclusive

    def test_sibling_children(self):
        from paddle_tpu.profiler import _exclusive_times

        parent = self._ev(0, 100, name="parent")
        c1 = self._ev(10, 20, name="c1")
        c2 = self._ev(50, 30, name="c2")
        excl = _exclusive_times([parent, c1, c2])
        assert excl[id(parent)] == 50      # 100 - 20 - 30

    def test_partial_overlap_not_subtracted(self):
        from paddle_tpu.profiler import _exclusive_times

        # b starts inside a but ends after it — NOT properly contained, so
        # nothing is subtracted (malformed traces degrade to inclusive)
        a = self._ev(0, 50, name="a")
        b = self._ev(40, 30, name="b")
        excl = _exclusive_times([a, b])
        assert id(a) not in excl
        assert id(b) not in excl

    def test_multi_pid_tid_timelines_independent(self):
        from paddle_tpu.profiler import _exclusive_times

        # identical time windows on two devices: each (pid, tid) timeline
        # nests independently — no cross-device subtraction
        p1_parent = self._ev(0, 100, pid=1, name="p1")
        p1_child = self._ev(10, 20, pid=1, name="c1")
        p2_span = self._ev(10, 20, pid=2, name="p2")
        t2_span = self._ev(5, 90, pid=1, tid=2, name="t2")
        excl = _exclusive_times([p1_parent, p1_child, p2_span, t2_span])
        assert excl[id(p1_parent)] == 80
        assert id(p2_span) not in excl
        assert id(t2_span) not in excl

    def test_events_without_dur_ignored(self):
        from paddle_tpu.profiler import _exclusive_times

        meta = {"ts": 0, "pid": 1, "tid": 1, "name": "meta"}
        span = self._ev(0, 10)
        assert _exclusive_times([meta, span]) == {}


def test_chrome_tracing_roundtrip(tmp_path, capsys):
    """export_chrome_tracing must round-trip every recorded span with its
    name/ts/dur into chrome://tracing's event format."""
    profiler.start_profiler()
    with profiler.RecordEvent("alpha"):
        with profiler.RecordEvent("beta"):
            pass
    live = profiler.events()
    path = str(tmp_path / "trace.json")
    profiler.stop_profiler(profile_path=path)
    capsys.readouterr()
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == [e["name"] for e in live]
    for got, src in zip(evs, live):
        assert got["ph"] == "X"
        assert got["ts"] == src["ts"] and got["dur"] == src["dur"]
        assert got["tid"] == src["tid"]


class TestMonitor:
    def test_stat_add(self):
        from paddle_tpu.core.monitor import StatRegistry, stat_add, stat_get

        stat_add("test_stat", 5)
        stat_add("test_stat", 7)
        assert stat_get("test_stat") == 12
        assert StatRegistry.instance().stats()["test_stat"] == 12


def test_device_profile_attributes_to_source():
    """profiler.device_profile (reference: per-op device tables +
    tools/timeline.py; device side via the jax profiler instead of
    CUPTI) must attribute exclusive device time to the operations of the
    op lowerings (on the CPU the plane names them by HLO instruction, the
    fc layers' `dot_general`). Runs in a subprocess so the profiler session, and the
    backend it hooks, are the child's own."""
    import os
    import subprocess
    import sys

    child = r'''
import numpy as np
import paddle_tpu as pt
from paddle_tpu import layers, profiler

main, startup = pt.Program(), pt.Program()
with pt.program_guard(main, startup):
    x = layers.data("x", [512])
    h = layers.fc(x, 512, act="relu")
    out = layers.reduce_mean(layers.fc(h, 512))
exe = pt.Executor(pt.CPUPlace())
scope = pt.Scope()
exe.run(startup, scope=scope, use_compiled=False)
feed = {"x": np.random.RandomState(0).randn(256, 512).astype(np.float32)}
exe.run(main, feed=feed, fetch_list=[out], scope=scope)
prof = profiler.device_profile(
    lambda: exe.run(main, feed=feed, fetch_list=[out], scope=scope),
    steps=2)
assert prof["ms_per_step"] > 0, prof
assert any("dot_general" in scope for scope, _ in prof["rows"]), prof["rows"]
print("DEVICE_PROFILE_OK")
'''
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=240)
    assert "DEVICE_PROFILE_OK" in r.stdout, (r.stdout[-500:],
                                             r.stderr[-1500:])
