"""The per-kernel readers: device seconds of one family of operations a run
of the main program, the paged-attention kernel's share of its roofline and
the collectives' share of busy time, by hand; and the collectives found in
a traced step on four virtual devices, none in a step on one."""

import pytest

from benchmark import flops, run
from benchmark.manifest import Manifest
from benchmark.readers import _kernel
from benchmark.runners import result

from . import toy

REAL = Manifest(toy.REPO)
XGLM = REAL.config_doc("xglm_1p7b")
PEAKS = flops.peaks("TPU v5 lite")


# the reader waits for its cell (PERF.md, Open questions): the entry a
# four-chip training cell brings with it
COLLECTIVE_ENTRY = {
    "name": "collective_share_of_busy.train", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "kernels and step program",
    "moves": "train_tokens_per_s", "workloads": ["toy_train"]}


def traced(kind, op_seconds, runs=120.0, **fields):
    return result(
        kind=kind, peaks=PEAKS, config=XGLM,
        trace={"window_s": 3.0, "busy_s": 2.46, "op_seconds": op_seconds,
               "programs": {"jit_decode_step_b8(1)": {"runs": runs,
                                                      "seconds": 2.2},
                            "jit_prefill_p256(2)": {"runs": 9.0,
                                                    "seconds": 0.1}}},
        **fields)


def test_a_familys_seconds_are_per_run_of_the_main_program():
    ctx = traced("serve", {"paged_attention": 1.218, "fusion": 1.026})
    assert _kernel.seconds(ctx, ("paged_attention",)) == 1.218
    assert _kernel.seconds(ctx, ("fusion", "paged")) \
        == pytest.approx(2.244)
    assert _kernel.seconds_per_run(ctx, "paged_attention") \
        == pytest.approx(1.218 / 120)
    assert _kernel.seconds(ctx, ("flash_fwd",)) is None
    assert _kernel.seconds_per_run(ctx, "flash_fwd") is None
    assert _kernel.seconds(result(kind="serve"), ("fusion",)) is None
    ctx.trace.pop("programs")          # a CPU trace: no program line
    assert _kernel.seconds_per_run(ctx, "paged_attention") is None


def test_paged_attention_roofline_by_hand():
    """PERF.md section 5 (my chip run, PR 25): 1.07 GB of live K/V a step,
    the kernel 1.218 s over 120 steps of a traced window."""
    read = REAL.reader("paged_attention_roofline")
    live = 1.07e9 / 393_216           # tokens, at 393,216 bytes of K/V each
    ctx = traced("serve", {"paged_attention": 1.218},
                 live_context_tokens=live)
    least_s = 1.07e9 / 819e9
    assert read(ctx) == pytest.approx(100 * least_s / (1.218 / 120))
    assert 12.0 < read(ctx) < 14.0
    # nothing to read: another kind, no kernel of that name, no live
    # context, an untraced run
    assert read(traced("train", {"paged_attention": 1.0},
                       live_context_tokens=live)) is None
    assert read(traced("serve", {"fusion": 1.0},
                       live_context_tokens=live)) is None
    assert read(traced("serve", {"paged_attention": 1.0})) is None
    assert read(result(kind="serve", live_context_tokens=live)) is None


def test_collective_share_of_busy_by_hand():
    read = REAL.reader("collective_share_of_busy.train")
    ops = {"fusion": 1.9, "all-reduce": 0.2, "all-gather-start": 0.01,
           "all-gather-done": 0.09, "reduce-scatter": 0.05,
           "collective-permute-done": 0.019, "all-to-all": 0.0,
           "reduce_sum": 0.5, "gather": 0.3}
    ctx = traced("train", ops)
    assert read(ctx) == pytest.approx(100 * 0.369 / 2.46)
    assert read(traced("train", {"fusion": 1.9, "reduce_sum": 0.5})) is None
    assert read(traced("serve", ops)) is None
    assert read(result(kind="train")) is None


@pytest.mark.parametrize("chips, mesh", [(4, {"4": {"dp": 2, "mp": 2}}),
                                         (1, None)])
def test_a_traced_step_reports_collectives_only_across_devices(
        tmp_path, chips, mesh):
    """The reader finds the partitioned step's all-reduces in the trace of
    four (virtual) devices and nothing in a step on one. The CPU's value
    means nothing (its worker threads share one plane); its presence does."""
    import jax

    if len(jax.devices()) < chips:
        pytest.skip("needs four (virtual) devices")
    root = toy.keep_cells(
        toy.make_root(str(tmp_path), chips={"toy_train": chips}, mesh=mesh,
                      extra_metric=COLLECTIVE_ENTRY),
        {"toy_train": "kernel_train"})
    assert Manifest(root).problems() == []
    out = run.run_cell(root, "kernel_train", seed=11, seconds=1.0,
                       trace=True, require_platform=None)
    assert out["correct"] is True
    assert out["device"]["count"] == chips
    got = out["metrics"].get("collective_share_of_busy.train")
    if chips == 4:
        assert got["unit"] == "%" and got["value"] > 0
        assert any(name.startswith("all-reduce")
                   for name, _ in out["breakdown"]["device_ops"])
    else:
        assert got is None
