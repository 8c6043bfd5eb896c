"""Test config: run on a virtual 8-device CPU mesh (no TPU contention).

Mirrors the reference's test strategy (SURVEY.md §4): multi-device tests run
single-process against mesh slices of the 8 virtual devices.
MUST set env before jax is imported anywhere.
"""

import os

# the suite runs on the CPU, and so does every child process a test
# starts (they inherit this environment)
os.environ["JAX_PLATFORMS"] = "cpu"
# every run compiles afresh: nothing a test sees depends on what an
# earlier run left in the persistent compile cache (core/compile_cache.py)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1 runs "
                   "(-m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: fault-injection / failure-path tests driven by "
                   "the core/faults.py harness (tools/chaos_check.py is "
                   "the CLI twin). Tier-1-safe: localhost sockets, "
                   "sub-second timeouts.")
    config.addinivalue_line(
        "markers", "serving: micro-batching serving-engine tests "
                   "(paddle_tpu/serving/). Tier-1-fast: in-process "
                   "client for engine tests, one ephemeral-port HTTP "
                   "smoke.")


@pytest.fixture
def scope():
    import paddle_tpu as pt

    return pt.Scope()


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Give every test fresh default programs + a fresh name generator,
    and clear any process-global mesh a test installed (a leaked mesh
    makes later single-device tests shard their feeds)."""
    import paddle_tpu as pt
    from paddle_tpu.core import ir, unique_name
    from paddle_tpu.parallel import mesh as mesh_mod

    old_main, old_startup = ir._main_program, ir._startup_program
    ir._main_program, ir._startup_program = ir.Program(), ir.Program()
    old_gen = unique_name.switch()
    old_mesh = mesh_mod._current_mesh
    mesh_mod._current_mesh = None
    yield
    unique_name.switch(old_gen)
    ir._main_program, ir._startup_program = old_main, old_startup
    mesh_mod._current_mesh = old_mesh


# Op-sweep modules run with the static program verifier gating every
# executor dispatch (FLAGS_verify_program, core/verify.py): the OpTest
# harness builds one program per op, so the whole registry's programs
# flow through the verifier's structure/dataflow/hazard/donation checks
# — any op whose desc wiring the verifier would mis-judge fails loudly
# here, keeping the lint trustworthy on real models.
_VERIFY_FLAG_MODULES = {
    "test_op_registry_sweep", "test_gate_smoke_execution",
    "test_ops_batch2", "test_ops_batch3", "test_ops_extended",
    "test_ops_round4", "test_ops_round5", "test_crf_ops",
    "test_pallas_serving_kernels",
}


@pytest.fixture(autouse=True)
def _verify_program_on_op_sweeps(request):
    mod = request.module.__name__.rsplit(".", 1)[-1]
    if mod not in _VERIFY_FLAG_MODULES:
        yield
        return
    from paddle_tpu.core import flags as _flags

    # the typed scoped-override API (PR 15): exact prior restored even
    # when the test body raises — no ad-hoc save/restore
    with _flags.overrides(verify_program=True):
        yield


# Concurrency-sanitizer opt-in (PT_SANITIZE_TESTS=1): the serving/
# cluster tier-1 modules — the most thread-dense surfaces — run with
# FLAGS_sanitize_locks=1, so every engine/router/cluster lock they
# construct is an instrumented core/analysis/lockdep.py lock: a
# lock-order inversion or a same-thread re-entry introduced by a new
# change raises LockOrderError inside the test instead of wedging a
# production router at 3 a.m. Off by default: the instrumented wrappers
# add per-acquire bookkeeping the rest of the suite shouldn't pay.
_SANITIZE_MODULES = {"test_serving", "test_cluster_serving"}


@pytest.fixture(autouse=True)
def _sanitize_locks_opt_in(request):
    if not os.environ.get("PT_SANITIZE_TESTS"):
        yield
        return
    mod = request.module.__name__.rsplit(".", 1)[-1]
    if mod not in _SANITIZE_MODULES:
        yield
        return
    from paddle_tpu.core import flags as _flags

    with _flags.overrides(sanitize_locks=True):
        yield


def rand(*shape, dtype=np.float32, seed=None):
    rng = np.random.RandomState(seed if seed is not None else 42)
    return rng.randn(*shape).astype(dtype)


# ---------------------------------------------------------------------------
# Execution-based op-coverage gate (round 5; VERDICT r4 weak #4)
#
# The old gate regex-searched test SOURCES, so an op named in a comment
# counted as covered. Now every process records the op types that actually
# flowed through the executors (core/executor.py EXECUTED_OP_TYPES), dumps
# them at session end, and the controller asserts
# registry ⊆ executed ∪ allowlist. Enforced only for full-suite runs (the
# sentinel below fires when the collected test count says "whole tests/
# directory"), so single-file invocations stay usable.
# ---------------------------------------------------------------------------

_COV_DIR_ENV = "PT_OP_COVERAGE_DIR"
if not os.environ.get(_COV_DIR_ENV):
    import tempfile as _tempfile

    # set BEFORE xdist spawns workers so every process shares the dir
    os.environ[_COV_DIR_ENV] = _tempfile.mkdtemp(prefix="pt_opcov_")

# Infra ops exercised through dedicated runtimes, not executor-visible ops
# (mirrors the justification list in test_op_registry_sweep.py).
_GATE_ALLOWLIST = {
    "listen_and_serv",              # PS server loop (pserver runtime)
    "distributed_lookup_table",     # io_callback body inside jit — the
    "distributed_lookup_table_grad",  # push/pull runs outside run_op
}


def pytest_sessionfinish(session, exitstatus):
    import glob as _glob
    import json as _json
    import uuid as _uuid

    covdir = os.environ.get(_COV_DIR_ENV)
    if not covdir or not os.path.isdir(covdir):
        return
    try:
        from paddle_tpu.core.executor import EXECUTED_OP_TYPES
    except Exception:
        EXECUTED_OP_TYPES = set()
    if EXECUTED_OP_TYPES:
        with open(os.path.join(covdir, f"{_uuid.uuid4().hex}.json"),
                  "w") as f:
            _json.dump(sorted(EXECUTED_OP_TYPES), f)
    # full-suite sentinel: any process that COLLECTED the whole suite
    # (workers collect everything under xdist) plants it
    if len(getattr(session, "items", []) or []) > 500 or \
            os.path.exists(os.path.join(covdir, "SENTINEL")):
        open(os.path.join(covdir, "SENTINEL"), "w").close()
    if hasattr(session.config, "workerinput"):
        return  # xdist worker: the controller does the assert
    import shutil as _shutil

    if not os.path.exists(os.path.join(covdir, "SENTINEL")):
        # partial run: no enforcement — and clean this session's dir so
        # dev loops don't accumulate /tmp/pt_opcov_* litter (workers
        # have already dumped by the time the controller gets here)
        _shutil.rmtree(covdir, ignore_errors=True)
        os.environ.pop(_COV_DIR_ENV, None)
        return
    if exitstatus not in (0,):
        _shutil.rmtree(covdir, ignore_errors=True)
        os.environ.pop(_COV_DIR_ENV, None)
        return  # failures already reported; don't stack a gate error
    executed = set()
    for path in _glob.glob(os.path.join(covdir, "*.json")):
        try:
            executed.update(_json.load(open(path)))
        except Exception:
            pass
    import paddle_tpu  # noqa: F401
    from paddle_tpu.core.registry import registered_ops

    missing = [op for op in registered_ops()
               if op not in executed and op not in _GATE_ALLOWLIST]
    _shutil.rmtree(covdir, ignore_errors=True)
    os.environ.pop(_COV_DIR_ENV, None)
    if missing:
        raise pytest.UsageError(
            f"EXECUTION coverage gate: {len(missing)} registered ops "
            f"never flowed through an executor during the suite: "
            f"{missing} — add a test that RUNS them (a textual mention "
            f"no longer counts)")
