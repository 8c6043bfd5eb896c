"""Every line of `program_fingerprints.json`: the jaxprs the served families'
step and prefill programs, the two trainers' steps and four shared ops trace
to at toy widths are the ones the table was written from. A PR that changes
one on purpose rewrites the table (the command is in the failure)."""

import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))

import program_fingerprints as fp  # noqa: E402

TABLE = fp.read_table()


@pytest.mark.parametrize("name", sorted(fp.CASES))
def test_program_is_the_tables(name):
    now = fp.CASES[name]()
    assert now == TABLE.get(name), (
        f"{name}: the table has {TABLE.get(name)}, this tree traces {now}. "
        f"If the program was meant to change, rewrite the table: "
        f"{fp.WRITE_COMMAND}")
