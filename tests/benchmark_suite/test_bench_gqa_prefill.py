"""`gqa_prefill_attention_busy_share.serve` by hand on canned traces: the
share where the trace holds the flash forward kernel, nothing where it does
not (the parent's programs keep that time inside `while`), and its entry in
the real manifest."""

import pytest

from benchmark import flops
from benchmark.manifest import Manifest
from benchmark.runners import result

from . import toy

METRIC = "gqa_prefill_attention_busy_share.serve"
CELL = "qwen3_next_80b_tp4ep4_serve_closed_c96"
# `device_ops` of cell 7's traced 3 s at the parent (ledger, PR 50) and the
# same with a kernel's seconds taken out of the loops'
PARENT = {"fusion": 0.934, "grouped_swiglu": 0.780, "while": 0.752,
          "paged_gqa_attention": 0.255}
CHANGE = dict(PARENT, **{"while": 0.55, "flash_fwd_window": 0.06})


def traced(op_seconds, kind="serve", busy_s=2.99):
    return result(kind=kind, peaks=flops.peaks("TPU v5 lite"),
                  trace={"window_s": 3.0, "busy_s": busy_s,
                         "op_seconds": op_seconds})


@pytest.mark.parametrize("case, ctx, want", [
    ("the_kernel_in_the_trace", traced(CHANGE), 100 * 0.06 / 2.99),
    ("two_instructions_of_the_kernel",
     traced(dict(PARENT, **{"flash_fwd_window": 0.04,
                            "flash_fwd_window.1": 0.03})),
     100 * 0.07 / 2.99),
    ("the_parents_programs", traced(PARENT), None),
    ("the_backward_kernels_are_not_it",
     traced({"flash_bwd_window_dq": 0.1, "flash_bwd_window_dkv": 0.2}),
     None),
    ("nothing_busy", traced(CHANGE, busy_s=0), None),
    ("a_trainers_forward", traced(CHANGE, kind="train"), None),
    ("no_trace", result(kind="serve"), None),
])
def test_the_share_by_hand(case, ctx, want):
    got = Manifest(toy.REPO).reader(METRIC)(ctx)
    assert got == (want if want is None else pytest.approx(want))


def test_the_entry_names_the_cell_that_builds_the_op_at_long_buckets():
    entry, = (m for m in Manifest(toy.REPO).doc["per_layer"]
              if m["name"] == METRIC)
    assert entry == {"name": METRIC, "unit": "%", "better": "lower",
                     "source": "device_trace",
                     "layer": "kernels and step program",
                     "moves": "serve_tokens_per_s", "workloads": [CELL]}
