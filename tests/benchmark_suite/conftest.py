"""One marker, on one test this directory already had.

`test_bench_step_ahead.test_the_manifest_is_sound_with_the_entry_at_its_end`
(PR 31) pins `step_ahead_share.serve` as the LAST entry of `per_layer` with
exactly two cells in its `workloads`. ISSUE 33 asks for the cell
`kimi_k2_dp_ep32_serve_closed_c96` in that list and for new per-layer
entries, which go at the end of theirs: the assertion cannot hold beside
them, and a file the benchmark already has is a `benchmark` PR's to edit.
What the test meant is held by
`test_bench_kimi_k2.test_step_ahead_share_lists_every_serving_cell_at_saturation`.
The marker is strict: a `benchmark` PR that repairs the test takes it out.
"""

import pytest

STALE = ("test_bench_step_ahead.py::"
         "test_the_manifest_is_sound_with_the_entry_at_its_end")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(STALE):
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="pins per_layer[-1] and two workloads; ISSUE 33's "
                       "entries supersede it (see this conftest)"))
